"""The ``serve_hybrid_moe`` configuration's benchmark files: its readers on a
synthetic record (and on records that lack what they read), its costs from
shapes, its configuration file against the public config's keys, and the
CPU rehearsal of its cell."""

import json
import os

import pytest

from perfbench import run
from perfbench.harness import costs_mimo_v2 as costs
from perfbench.harness.spec import BENCH_DIR, REPO_DIR, Cell, load_module

CELL = "mimo_reasoning_saturated"
READERS = ("hybrid_decode_roofline", "moe_expert_roofline",
           "window_attention_roofline", "moe_pairs_per_step",
           "moe_load_max_over_mean", "kv_window_pool_peak")


def _config():
    with open(os.path.join(BENCH_DIR, "configs",
                           "mimo_v2p5_ep16_serve_bf16.json")) as f:
        return json.load(f)


def _read(name, rec):
    return load_module("metrics", name).read(rec)


def _rec():
    """Two traced decode-only steps of 10 ms device time each (4 ms in the
    grouped matmul, 1 ms in the window kernel), one prefill step between
    them, 128 rows of 900 positions."""
    def ops(t):
        return [("%gmm.1 = bf16[1024,4096]{1,0} custom-call(..)", t, t + .002),
                ("%gmm.2 = bf16[1024,4096]{1,0} custom-call(..)", t + .002,
                 t + .004),
                ("%paged_gqa_decode_window.3 = bf16[128,64,128]{2,1,0} "
                 "custom-call(..)", t + .004, t + .005),
                ("%fusion.7 = bf16[128,4096]{1,0} fusion(..), kind=kLoop",
                 t + .005, t + .010)]
    trace = {"devices": {0: {"ops": ops(100.001) + ops(100.031)
                             + ops(100.051)}},
             "spans": [], "run_clock_offset_s": 90.0}
    return {"kind": "serve", "model": _config(), "weight_bytes": 2,
            "cache_bytes": 2, "device": {"kind": "TPU v5 lite"},
            "steps": [(10.0, 10.02, 0, 128, 7000, 5, 128 * 900),
                      (10.03, 10.05, 1, 128, 7000, 5, 128 * 900),
                      (10.05, 10.07, 0, 128, 7000, 5, 128 * 900)],
            "trace": trace,
            "trace_summary": {"t0": 100.0, "t1": 100.1, "busy_s": 0.03,
                              "window_s": 0.1},
            "hybrid": {
                "telemetry0": {"steps": 10, "moe_pairs_held_sum": 600.0,
                               "moe_load_max_sum": 90.0},
                "telemetry1": {"steps": 110, "moe_pairs_held_sum": 7000.0,
                               "moe_load_max_sum": 990.0},
                "window_blocks_peak": 1100, "window_blocks_total": 1152}}


def test_costs_follow_the_published_shapes():
    cfg = _config()
    assert costs.attention_params(cfg, False) == 89_128_960
    assert costs.attention_params(cfg, True) == 94_371_840
    assert costs.expert_params(cfg) == 25_165_824
    assert costs.kv_row_bytes(cfg, False, 2) == 2560
    assert costs.kv_row_bytes(cfg, True, 2) == 5120
    assert costs.count_layers(cfg, window=True) == 5
    assert costs.count_layers(cfg, moe=True) == 6
    assert 15.6 < costs.experts_touched(cfg, 128) < 15.8
    assert costs.experts_touched(cfg, 0) == 0
    # ISSUE 28's arithmetic: ~6.7 GB of weights + ~1 GB of live K/V
    step = costs.decode_step_min_bytes(cfg, 2, 2, 128, 128 * 900)
    assert 7.4e9 < step < 8.0e9
    # a window layer's bytes stop growing at the window
    assert costs.window_layer_kv_bytes(cfg, 128, 900, 2) == (
        costs.window_layer_kv_bytes(cfg, 128, 128, 2))


def test_readers_on_a_synthetic_record():
    rec = _rec()
    cfg = rec["model"]
    bw = 819e9
    need = costs.decode_step_min_bytes(cfg, 2, 2, 128, 128 * 900)
    assert _read("hybrid_decode_roofline", rec) == pytest.approx(
        100 * (2 * need / bw) / 0.020)
    experts = 6 * costs.expert_layer_bytes(cfg, 128, 2)
    assert _read("moe_expert_roofline", rec) == pytest.approx(
        100 * (2 * experts / bw) / 0.008)
    window = 5 * costs.window_layer_kv_bytes(cfg, 128, 900, 2)
    assert _read("window_attention_roofline", rec) == pytest.approx(
        100 * (2 * window / bw) / 0.002)
    assert _read("moe_pairs_per_step", rec) == pytest.approx(64.0)
    assert _read("moe_load_max_over_mean", rec) == pytest.approx(9.0 / 4.0)
    assert _read("kv_window_pool_peak", rec) == pytest.approx(
        100 * 1100 / 1152)


@pytest.mark.parametrize("strip", ["hybrid", "trace", "kernels"])
def test_readers_find_nothing_where_the_program_lacks_it(strip):
    rec = _rec()
    if strip == "hybrid":          # another runner's record, or the parent's
        del rec["hybrid"]
        names = READERS
    elif strip == "trace":         # an untraced run
        rec["trace"] = rec["trace_summary"] = None
        names = READERS[:3]
    else:                          # the kernels are not on the path
        rec["trace"]["devices"][0]["ops"] = [
            e for e in rec["trace"]["devices"][0]["ops"] if "fusion" in e[0]]
        names = READERS[1:3]
    for name in names:
        assert _read(name, rec) is None, name


def test_configuration_file_keeps_the_public_keys():
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        public = next(d for d in map(json.loads, f)
                      if d["name"] == "MiMo-V2.5")
    assert cfg["source"] == public["source_url"]
    changed = {k for k, v in public["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert set(cfg["reduced"]) == changed | {"experts_held"}
    assert cfg["n_routed_experts"] == 256 and cfg["experts_held"] == 16
    assert cfg["vocab_size"] * 8 == public["config"]["vocab_size"]
    assert "16 chips" in cfg["deployment"]


def test_benchmark_entries_resolve():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["kind"] == "serve_hybrid_moe"
    assert cell.traffic["clients"] == 256
    traced = {m["name"] for m in cell.metric_entries(traced=True)}
    assert set(READERS) <= traced and "device_idle.mimo" in traced
    for name in traced:
        assert hasattr(cell.reader(name), "read")
    assert {m["name"] for m in cell.metric_entries(traced=False)} == {
        "tokens_per_s", "setup_s"}
    with open(os.path.join(REPO_DIR, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


def test_rehearsal_of_the_cell(capsys):
    rc = run.main(["--workload", CELL, "--seed", "2800000999", "--seconds",
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


def _toy_check(seed=2800000998):
    """``served_logits`` and ``compare`` on a toy scheduler, as the
    rehearsal's set-up runs them."""
    import paddle_tpu  # noqa: F401  (the CPU is conftest's)
    from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

    config = _config()
    config.update(config["rehearsal"])
    runner = load_module("runners", "serve_hybrid_moe")
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
    assert served["window_released"] > 0 and served["pools_whole"]
    assert served["step_tokens_agree"] == 1.0
    long, short = served["requests"]
    assert long["logits"].shape[0] == short["logits"].shape[0] == 21
    assert len(long["tokens"]) == 40 + 20 and len(short["tokens"]) == 3 + 20
    assert [c.shape for c in short["choices"]] == [(23, 4)] * 3


def test_check_refuses_a_window_page_released_early(monkeypatch):
    """What the check is there for: the scheduler's own bookkeeping wrong
    with every row live. A window class that lets go of each page 16
    positions too soon changes no count and no shape, only the logits."""
    from paddle_tpu.serving import ContinuousBatchingScheduler as Sched

    span = Sched._window_span

    def early(self, pos):
        first, last = span(self, pos)
        return min(first + 1, last), last

    monkeypatch.setattr(Sched, "_window_span", early)
    served, out, notes = _toy_check()
    assert not out["ok"] and notes[-1].endswith("FAILED")
    assert out["err_of_scale"] > 0.001
    assert served["pools_whole"] and served["step_tokens_agree"] == 1.0


def test_controls_come_out_on_the_right_side(capsys):
    from perfbench import controls_mimo_v2 as controls

    assert controls.main(["--seeds", "5", "--rehearse-cpu"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert {(l["fault"], l["ok"]) for l in lines} == {
        ("none", True), ("kv_8bit", False), ("no_sink", False)}
