"""The ``serve_latent_moe`` configuration's benchmark files: its costs from
shapes, its readers on a synthetic record (and on records that lack what
they read), its configuration file against the public config's keys, the CPU
rehearsal of its cell, its check on a toy scheduler (sound, and with the
served path broken), and its controls."""

import json
import os

import pytest

from perfbench import run
from perfbench.harness import costs_joyai_flash as costs
from perfbench.harness.spec import BENCH_DIR, REPO_DIR, Cell, load_module

CELL = "joyai_reasoning_0p8knee"
READERS = ("mla_kernel_roofline", "latent_decode_roofline",
           "moe_gmm_roofline", "moe_shared_share", "kv_latent_row_bytes",
           "moe_pairs_per_step", "moe_load_max_over_mean")


def _config():
    with open(os.path.join(BENCH_DIR, "configs",
                           "joyai_flash_ep16_serve_bf16.json")) as f:
        return json.load(f)


def _read(name, rec):
    return load_module("metrics", name).read(rec)


def _rec():
    """Two traced decode-only steps of 10 ms device time each (4 ms in the
    latent kernel, 2 ms in the grouped matmul, 1 ms in the shared expert's
    two products), one prefill step between them, 100 rows of 2,500
    positions."""
    def ops(t):
        return [("%paged_mla_decode.3 = bf16[128,32,512]{2,1,0} "
                 "custom-call(..)", t, t + .004),
                ("%gmm.1 = bf16[128,1536]{1,0} custom-call(..)", t + .004,
                 t + .005),
                ("%gmm.2 = bf16[128,2048]{1,0} custom-call(..)", t + .005,
                 t + .006),
                ("%fusion.5 = bf16[128,768]{1,0} fusion(bf16[128,2048]{1,0} "
                 "%x, bf16[2048,768]{1,0} %p.1, bf16[2048,768]{1,0} %p.2), "
                 "kind=kOutput", t + .006, t + .0065),
                ("%fusion.6 = bf16[128,2048]{1,0} fusion(bf16[128,768]{1,0} "
                 "%fusion.5, bf16[768,2048]{1,0} %p.3), kind=kOutput",
                 t + .0065, t + .007),
                ("%fusion.7 = bf16[128,2048]{1,0} fusion(..), kind=kLoop",
                 t + .007, t + .010)]
    trace = {"devices": {0: {"ops": ops(100.001) + ops(100.031)
                             + ops(100.051)}},
             "spans": [], "run_clock_offset_s": 90.0}
    return {"kind": "serve", "model": _config(), "weight_bytes": 2,
            "cache_bytes": 2, "device": {"kind": "TPU v5 lite"},
            "steps": [(10.0, 10.02, 0, 100, 7000, 5, 100 * 2500),
                      (10.03, 10.05, 1, 100, 7000, 5, 100 * 2500),
                      (10.05, 10.07, 0, 100, 7000, 5, 100 * 2500)],
            "trace": trace,
            "trace_summary": {"t0": 100.0, "t1": 100.1, "busy_s": 0.03,
                              "window_s": 0.1},
            "hybrid": {
                "telemetry0": {"steps": 10, "moe_pairs_held_sum": 600.0,
                               "moe_load_max_sum": 90.0},
                "telemetry1": {"steps": 110, "moe_pairs_held_sum": 7000.0,
                               "moe_load_max_sum": 990.0}},
            "latent": {"kv_bytes_per_token": 16640.0}}


def test_costs_follow_the_published_shapes():
    cfg = _config()
    # ISSUE 37's arithmetic: a 576-wide row of 1,152 B, 69.6 KFLOP a cached
    # position a layer (60 FLOP/B), 26.3 M of attention, 4.72 M an expert
    assert costs.latent_row_bytes(cfg, 2) == 1152
    assert costs.latent_flops_per_position(cfg) == 69_632
    assert costs.attention_params(cfg) == (
        3_145_728 + 9_437_184 + 1_179_648 + 4_194_304 + 8_388_608)
    assert costs.expert_params(cfg) == 4_718_592
    assert costs.expert_layers(cfg) == 12
    assert 15.3 < costs.experts_touched(cfg, 100) < 15.4
    assert costs.experts_touched(cfg, 0) == 0
    assert costs.latent_bytes(cfg, 250_000, 2) == 288_000_000
    # 0.965 GB of weights read whole + 12 x 15.3 touched experts of 9.4 MB
    # (1.74 GB) + 13 layers x 250 K positions x 1,152 B (3.74 GB)
    step = costs.decode_step_min_bytes(cfg, 2, 2, 100, 250_000)
    assert 6.40e9 < step < 6.50e9
    fixed = costs.decode_step_min_bytes(cfg, 2, 2, 0, 0)
    assert fixed == 2 * 482_541_568


def test_readers_on_a_synthetic_record():
    rec = _rec()
    cfg = rec["model"]
    bw = 819e9
    latent = 13 * costs.latent_bytes(cfg, 250_000, 2)
    assert _read("mla_kernel_roofline", rec) == pytest.approx(
        100 * (2 * latent / bw) / 0.008)
    need = costs.decode_step_min_bytes(cfg, 2, 2, 100, 250_000)
    assert _read("latent_decode_roofline", rec) == pytest.approx(
        100 * (2 * need / bw) / 0.020)
    experts = 12 * costs.expert_layer_bytes(cfg, 100, 2)
    assert _read("moe_gmm_roofline", rec) == pytest.approx(
        100 * (2 * experts / bw) / 0.004)
    assert _read("moe_shared_share", rec) == pytest.approx(
        100 * 0.002 / 0.006)
    assert _read("kv_latent_row_bytes", rec) == 16640
    assert _read("moe_pairs_per_step", rec) == pytest.approx(64.0)
    assert _read("moe_load_max_over_mean", rec) == pytest.approx(9.0 / 4.0)


@pytest.mark.parametrize("strip", ["groups", "trace", "kernels", "mimo"])
def test_readers_find_nothing_where_the_program_lacks_it(strip):
    rec = _rec()
    if strip == "groups":          # another runner's record, or the parent's
        del rec["hybrid"], rec["latent"]
        names = READERS
    elif strip == "trace":         # an untraced run
        rec["trace"] = rec["trace_summary"] = None
        names = READERS[:4]
    elif strip == "kernels":       # the kernels are not on the path
        rec["trace"]["devices"][0]["ops"] = [
            e for e in rec["trace"]["devices"][0]["ops"] if "kLoop" in e[0]]
        names = READERS[:1] + READERS[2:4]
    else:                          # a configuration of another family
        with open(os.path.join(BENCH_DIR, "configs",
                               "mimo_v2p5_ep16_serve_bf16.json")) as f:
            rec["model"] = json.load(f)
        names = READERS[:4]
    for name in names:
        assert _read(name, rec) is None, name


def test_configuration_file_keeps_the_public_keys():
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        public = next(d for d in map(json.loads, f)
                      if d["name"] == "JoyAI-LLM-Flash")
    assert cfg["source"] == public["source_url"]
    changed = {k for k, v in public["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert set(cfg["reduced"]) == changed | {"experts_held"}
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256, "experts_held": 256,
                                "vocab_size": 129280}
    assert cfg["n_routed_experts"] == 256 and cfg["experts_held"] == 16
    assert cfg["vocab_size"] * 8 == public["config"]["vocab_size"]
    assert cfg["num_hidden_layers"] == 1 + 12 and "16 chips" in cfg["deployment"]
    assert any("multi-token-prediction" in a for a in cfg["assumed"])


def test_benchmark_entries_resolve():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["kind"] == "serve_latent_moe"
    arrivals = cell.traffic["arrivals"]
    assert arrivals["rate_per_s"] == pytest.approx(
        0.8 * arrivals["knee_per_s"], rel=0.02)
    assert cell.traffic["ramp"]["residents"] <= 112
    traced = {m["name"] for m in cell.metric_entries(traced=True)}
    assert set(READERS[:5]) <= traced
    assert {r + ".joyai" for r in READERS[5:]} <= traced
    assert "device_idle.joyai" in traced and len(traced) == 28
    for name in traced:
        assert hasattr(cell.reader(name), "read")
    assert {m["name"] for m in cell.metric_entries(traced=False)} == {
        "tpot_p50_ms", "setup_s"}
    with open(os.path.join(REPO_DIR, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


def test_rehearsal_of_the_cell(capsys):
    rc = run.main(["--workload", CELL, "--seed", "3700000999", "--seconds",
                   "2", "--trace", "1", "--trace-seconds", "0.5",
                   "--rehearse-cpu"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    counts = json.loads(next(x for x in out if "rehearsal counts" in x)
                        .split("counts: ", 1)[1])
    assert counts["compiles_in_window"] == 0 and counts["tokens"] > 0
    assert any("through the scheduler" in x and "4 live rows" in x
               and x.endswith(": ok") for x in out)


def _toy_check(seed=3700000998):
    """``served_logits`` and ``compare`` on a toy scheduler, as the
    rehearsal's set-up runs them."""
    import paddle_tpu  # noqa: F401  (the CPU is conftest's)
    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    config = _config()
    config.update(config["rehearsal"])
    runner = load_module("runners", "serve_latent_moe")
    cfg, model = runner.build_model(config, seed)
    sched = ContinuousBatchingScheduler(
        model, SchedulerConfig(**config["scheduler"]))
    served = runner.served_logits(model, cfg, config, sched, seed)
    notes = []
    out = runner.compare(served, runner.reference.weights_of(model), config,
                         notes.append)
    return served, out, notes


def test_check_goes_through_the_scheduler_and_holds():
    served, out, _ = _toy_check()
    assert out["ok"] and served["live_rows"] == 4
    assert served["pools_whole"] and served["step_tokens_agree"] == 1.0
    long, short = served["requests"]
    assert long["logits"].shape[0] == short["logits"].shape[0] == 21
    assert len(long["tokens"]) == 40 + 20 and len(short["tokens"]) == 3 + 20
    assert [c.shape for c in short["choices"]] == [(23, 4)] * 2


def test_check_refuses_a_latent_row_written_one_position_late(monkeypatch):
    """What the check is there for: the served path's cache wrong with every
    row live. A decode step that writes its token's row one position late
    changes no count and no shape, only the logits."""
    from paddle_tpu.models import kv_cache

    write = kv_cache._latent_write_raw

    def late(rows, buf, pos, *table):
        if rows.shape[1] != 1:
            return write(rows, buf, pos, *table)
        buf2, pos2 = write(rows, buf, pos + 1, *table)
        return buf2, pos2 - 1

    monkeypatch.setattr(kv_cache, "_latent_write_raw", late)
    served, out, notes = _toy_check()
    assert not out["ok"] and notes[-1].endswith("FAILED")
    assert out["err_of_scale"] > 0.001
    assert served["pools_whole"] and served["step_tokens_agree"] == 1.0


def test_controls_come_out_on_the_right_side(capsys):
    from perfbench import controls_joyai_flash as controls

    assert controls.main(["--seeds", "5", "--rehearse-cpu"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert {(l["fault"], l["ok"]) for l in lines} == {
        ("none", True)} | {(f, False) for f in controls.FAULTS if f != "none"}
