"""In-step profiling (PR 17): named-region device-time attribution
inside the compiled decode/train programs, plus the zero-sync on-device
telemetry block.

Three tiers:

- canned-fixture parser tests (``tests/fixtures/stepprofile_*``): the
  HLO region/bytes parsers, the trace join, the jvp-wrapper and
  module-suffix resolutions, the byte-weighted naming-drift fallback,
  aux-module exclusion, and the in-step roofline math — all pure
  functions, no device work;
- the ``region-manifest`` lint in both directions (repo clean, seeded
  violations flagged);
- live smoke: an on-demand ``capture_step_profile`` over a real serving
  scheduler, and the load-bearing invariant that flipping
  ``enable_step_telemetry`` never changes a generated token or compiles
  an extra program — at dispatch_depth {0, 2} and tp {1, 2}.
"""

import gzip
import json
import os
import shutil
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.observability.step_profile import (
    REGION_MANIFEST,
    StepProfiler,
    attribute_trace,
    load_trace_events,
    parse_hlo_instruction_bytes,
    parse_hlo_instruction_regions,
    region,
)
from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig
from tools.graft_lint.regioncheck import check_regions, load_manifest_static

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """Serving decode programs must compile fresh: XLA:CPU AOT replay
    corrupts their numerics (same fence as test_serving_sched)."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _fixture_hlo() -> str:
    with open(os.path.join(FIXTURES, "stepprofile_module.hlo.txt")) as f:
        return f.read()


def _fixture_events():
    with open(os.path.join(FIXTURES, "stepprofile_trace.json")) as f:
        doc = json.load(f)
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


# --------------------------------------------------- HLO parser (canned)

def test_parse_hlo_regions_paths_and_jvp_wrapper():
    module, regions = parse_hlo_instruction_regions(_fixture_hlo())
    assert module == "jit_step"
    # transform wrappers (jvp(rgn_kv_gather)) still count as components
    assert regions["gather.1"] == ("attention", "kv_gather")
    assert regions["dot.1"] == ("attention",)
    assert regions["dot.2"] == ("mlp",)
    assert regions["sort.1"] == ("sampling",)
    # op_name present but no region component -> () = unattributed time
    assert regions["add.1"] == ()
    # no op_name metadata at all -> not in the map
    assert "p0.1" not in regions and "tuple.3" not in regions


def test_parse_hlo_bytes():
    nb = parse_hlo_instruction_bytes(_fixture_hlo())
    assert nb["gather.1"] == 4 * 64 * 4      # f32[4,64]
    assert nb["dot.1"] == 4 * 32 * 4
    assert nb["copy.2"] == 4 * 4             # f32[4]
    assert nb["p0.1"] == 4 * 8 * 4
    assert "tuple.3" not in nb               # tuple-shaped: skipped


# ------------------------------------------------- attribution (canned)

def _fixture_programs():
    module, regions = parse_hlo_instruction_regions(_fixture_hlo())
    nb = parse_hlo_instruction_bytes(_fixture_hlo())
    primary = {"name": "decode", "module": module, "regions": regions,
               "nbytes": nb, "flops": 1.0e6, "bytes_accessed": 2.0e6,
               "primary": True}
    # same-module collision (prefill buckets jit the same function):
    # maps dot.1 to a DIFFERENT region; list order must resolve it to
    # the primary's map
    prefill = {"name": "prefill", "module": module,
               "regions": {"dot.1": ("mlp",)}}
    return [primary, prefill]


def test_attribute_trace_fixture_end_to_end():
    out = attribute_trace(_fixture_events(), _fixture_programs())
    total = 30 + 20 + 25 + 5 + 10 + 12 + 8
    assert out["total_device_time_us"] == pytest.approx(total)
    assert out["unattributed_us"] == pytest.approx(10)      # add.1: ()
    assert out["coverage"] == pytest.approx((total - 10) / total, abs=1e-5)
    # shares sum to coverage, never renormalized to 1
    assert sum(out["region_shares"].values()) == pytest.approx(
        out["coverage"], abs=1e-4)
    rt = out["region_time_us"]
    # innermost wins the leaf: gather.1 (attention/kv_gather) is
    # kv_gather's; copy.7 is naming drift -> byte-weighted fallback over
    # the unmatched copy.* map entries (1024B -> kv_gather, 16B -> mlp)
    assert rt["kv_gather"] == pytest.approx(30 + 12 * 1024 / 1040,
                                            abs=1e-2)
    assert rt["mlp"] == pytest.approx(25 + 12 * 16 / 1040, abs=1e-2)
    # dot.1 resolves against the PRIMARY program's map despite the
    # colliding prefill row, and module "jit_step.1" resolves to
    # "jit_step" via the uniquifier-suffix fallback (20 + 8)
    assert rt["attention"] == pytest.approx(28, abs=1e-2)
    assert rt["sampling"] == pytest.approx(5, abs=1e-2)
    # outermost wins the group share
    assert out["group_shares"]["attention"] == pytest.approx(
        (30 + 20 + 8 + 12 * 1024 / 1040) / total, abs=1e-4)
    # device time in modules owned by no profiled program is reported,
    # not silently dropped — and excluded from the coverage denominator
    assert out["aux_modules"] == {"jit__threefry_split": 100.0}
    prog = out["programs"]["decode"]
    assert prog["events"] == 7
    assert prog["executions"] == 2           # dot.1 ran twice
    assert prog["step_device_time_s"] == pytest.approx(total / 2 * 1e-6)
    assert out["programs"]["prefill"]["events"] == 0


def test_attribute_trace_roofline_decomposition(monkeypatch):
    # on CPU there are no peaks: the byte decomposition is there, the
    # utilisation is ABSENT rather than computed against an invented peak
    out = attribute_trace(_fixture_events(), _fixture_programs())
    roof = out["decode_roofline"]
    assert roof["program"] == "decode"
    assert roof["flops"] == 1.0e6 and roof["bytes_accessed"] == 2.0e6
    for key in ("bandwidth_util", "mfu", "chip", "bandwidth_util_by_region"):
        assert key not in roof
    rs = out["programs"]["decode"]["region_shares"]
    for r, share in rs.items():
        assert roof["region_bytes_est"][r] == int(share * 2.0e6)

    # with a chip's row from the peaks table the utilisation decomposes
    from paddle_tpu.observability import program_inventory as pi

    v5e = pi.chip_specs("TPU v5 lite")
    monkeypatch.setattr(pi, "chip_specs", lambda *a: v5e)
    roof = attribute_trace(_fixture_events(),
                           _fixture_programs())["decode_roofline"]
    assert 0.0 < roof["bandwidth_util"] <= 1.0
    assert roof["chip"]["device_kind"] == "TPU v5 lite"
    for r, share in rs.items():
        assert roof["bandwidth_util_by_region"][r] == pytest.approx(
            share * roof["bandwidth_util"], abs=1e-5)
    # estimates decompose the measured step: never exceed the whole
    assert sum(roof["region_bytes_est"].values()) <= 2.0e6


def test_load_trace_events_reads_newest_gz(tmp_path):
    d = tmp_path / "plugins" / "profile" / "2026_08_06"
    d.mkdir(parents=True)
    with open(os.path.join(FIXTURES, "stepprofile_trace.json"), "rb") as f:
        raw = f.read()
    with gzip.open(d / "host.trace.json.gz", "wb") as f:
        f.write(raw)
    events = load_trace_events(str(tmp_path))
    assert len(events) == len(_fixture_events())   # complete events only
    assert all(e["ph"] == "X" for e in events)
    assert load_trace_events(str(tmp_path / "empty")) == []


# ------------------------------------------------------- region wrapper

def test_region_rejects_undeclared_name():
    with pytest.raises(ValueError, match="REGION_MANIFEST"):
        with region("not_a_region"):
            pass
    with region("attention"):      # declared: plain scope, no error
        pass


# ------------------------------------------------- region-manifest lint

def test_region_lint_repo_clean():
    root = os.path.join(REPO, "paddle_tpu")
    manifest = load_manifest_static(root)
    # the static (ast) read and the imported manifest must agree
    assert manifest == REGION_MANIFEST
    report = check_regions(root, manifest)
    assert report["ok"], report
    # every manifest entry is annotated somewhere
    assert sorted(report["regions_annotated"]) == sorted(manifest)


def test_region_lint_flags_seeded_violations(tmp_path):
    pkg = tmp_path / "fakepkg"
    (pkg / "observability").mkdir(parents=True)
    (pkg / "observability" / "step_profile.py").write_text(
        'REGION_MANIFEST = {\n'
        '    "used": {"owner": "x", "category": "Forward"},\n'
        '    "stale_one": {"owner": "x", "category": "Forward"},\n'
        '    "bad": {},\n'
        '}\n')
    (pkg / "engine.py").write_text(
        'def f(name):\n'
        '    with region("used"):\n'
        '        pass\n'
        '    with region("bad"):\n'
        '        pass\n'
        '    with region("undeclared_x"):\n'
        '        pass\n'
        '    with region(name):\n'
        '        pass\n')
    report = check_regions(str(pkg), load_manifest_static(str(pkg)))
    assert not report["ok"]
    assert report["undeclared"] == ["undeclared_x"]
    assert report["stale"] == ["stale_one"]
    assert report["malformed_entries"] == ["bad"]
    [dyn] = report["dynamic_sites"]
    assert dyn["arg"] == "name" and dyn["file"].endswith("engine.py")


def test_region_lint_registered_in_graft_lint():
    from tools.graft_lint import ALL_CHECKERS

    rules = [c.rule for c in ALL_CHECKERS]
    assert "region-manifest" in rules and "span-manifest" in rules


# ------------------------------------------------------------ live smoke

def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 120, int(k)) for k in rng.integers(4, 9, n)]


@pytest.fixture(scope="module")
def profiled_sched():
    """One scheduler captured mid-decode — shared by the capture /
    endpoint / postmortem tests (the trace is the expensive part)."""
    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=1))
    sched = ContinuousBatchingScheduler(model, SchedulerConfig(
        max_num_seqs=2, max_seq_len=64, block_size=8, max_new_tokens=8))
    for p in _prompts(2):
        sched.add_request(p, max_new_tokens=40)
    for _ in range(4):                     # compile + fill the token grid
        sched.step()
    n_before = sched.num_programs()
    summary = sched.capture_step_profile(steps=4)
    n_after = sched.num_programs()
    while sched.has_unfinished():
        sched.step()
    yield sched, summary, (n_before, n_after)
    sched.shutdown()


def test_capture_live_attributes_decode_regions(profiled_sched):
    sched, summary, (n_before, n_after) = profiled_sched
    assert summary["enabled"], summary.get("error")
    assert summary["trace_events"] > 0
    # capture is observation only: zero new compiled programs
    assert n_after == n_before
    shares = summary["region_shares"]
    for r in ("kv_gather", "attention", "mlp", "sampling"):
        assert shares.get(r, 0.0) > 0.0, (r, shares)
    assert sum(shares.values()) == pytest.approx(summary["coverage"],
                                                 abs=1e-3)
    assert summary["coverage"] >= 0.5, summary
    # a CPU capture decomposes bytes by region but states no utilisation
    roof = summary.get("decode_roofline")
    assert roof and roof["region_bytes_est"]
    assert "bandwidth_util" not in roof
    assert "bandwidth_util_by_region" not in roof


def test_capture_attributes_chunk_and_verify_regions():
    """Chunked prefill and speculation on, and two long prompts arriving
    for the one free slot just before the captured steps: the chunk
    program and the verify grid run inside the window, their regions
    show in the attribution, and the capture compiles nothing."""
    rng = np.random.default_rng(0)
    paddle.seed(7)
    sched = ContinuousBatchingScheduler(
        GPTForCausalLM(gpt_tiny(num_layers=1)),
        SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8,
                        prefill_chunk_size=16, spec_k=3))
    # every program shape once, one request at a time (a random context
    # alone takes the no-proposal decode program)
    sched.generate([rng.integers(0, 1000, 20)], max_new_tokens=4)
    pat = rng.integers(2, 40, 5)
    sched.add_request(np.concatenate([pat, pat]), max_new_tokens=24)
    for _ in range(4):
        sched.step()
    for _ in range(2):
        sched.add_request(rng.integers(0, 1000, 48), max_new_tokens=4)
    n_before = sched.num_programs()
    summary = sched.capture_step_profile(steps=6)
    assert sched.num_programs() == n_before
    while sched.has_unfinished():
        sched.step()
    sched.shutdown()
    assert summary["enabled"], summary.get("error")
    # the chunk program wraps a whole forward, so its inner operations
    # attribute to the nested leaves under the prefill_chunk group
    seen = {**summary["group_shares"], **{
        k: v for k, v in summary["region_shares"].items() if v > 0}}
    assert seen.get("prefill_chunk", 0.0) > 0.0, summary
    assert seen.get("spec_verify", 0.0) > 0.0, summary
    assert sched.spec_stats()["verify_steps"] > 0


def test_capture_feeds_endpoint_and_postmortem(profiled_sched):
    sched, summary, _ = profiled_sched
    # postmortem bundles attach the LATEST capture (capture-on-alarm)
    bundle = sched.postmortems.capture("test", "seeded", force=True)
    assert bundle["step_profile"]["coverage"] == summary["coverage"]
    # /debug/stepprofile serves the same state without touching devices
    ep = sched.start_endpoint()
    try:
        idx = json.loads(urllib.request.urlopen(
            f"{ep.url}/debug", timeout=10).read().decode())
        assert "/debug/stepprofile" in idx["routes"]
        doc = json.loads(urllib.request.urlopen(
            f"{ep.url}/debug/stepprofile", timeout=10).read().decode())
        [state] = [v for k, v in doc.items() if k.startswith("scheduler")]
        assert state["telemetry_enabled"] is True
        assert state["last_capture"]["coverage"] == summary["coverage"]
        assert state["telemetry"]["steps"] > 0
    finally:
        ep.stop()


def test_telemetry_snapshot_fields(profiled_sched):
    sched, _, _ = profiled_sched
    snap = sched.telemetry_snapshot()
    assert 0.0 < snap["occupancy"] <= 1.0
    assert snap["kv_blocks"] > 0
    assert 0.0 < snap["mean_max_prob"] <= 1.0
    assert snap["mean_entropy"] >= 0.0
    assert snap["steps"] > 0


def _generate(depth, telemetry, tp=None, seed=7):
    from paddle_tpu.serving.sharded import TensorParallelSharding

    paddle.seed(seed)
    model = GPTForCausalLM(gpt_tiny(num_layers=1))
    sharding = TensorParallelSharding(tp=tp) if tp else None
    sched = ContinuousBatchingScheduler(
        model,
        SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8,
                        dispatch_depth=depth,
                        enable_step_telemetry=telemetry),
        sharding=sharding)
    outs = sched.generate(_prompts(3), max_new_tokens=6)
    n = sched.num_programs()
    sched.shutdown()
    return outs, n


def test_telemetry_token_identity_and_program_count():
    """The tentpole invariant: the telemetry block rides the compiled
    step's existing outputs — switching it off changes neither a token
    nor the compiled-program count, at sync and dispatch-ahead depths."""
    ref, _ = _generate(depth=0, telemetry=True)
    for depth in (0, 2):
        on, n_on = _generate(depth=depth, telemetry=True)
        off, n_off = _generate(depth=depth, telemetry=False)
        assert n_on == n_off
        for a, b, c in zip(ref, on, off):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)


def test_telemetry_token_identity_sharded():
    """Same invariant across the tp mesh: tp in {1, 2} with telemetry
    on/off all decode the identical token streams."""
    ref, _ = _generate(depth=0, telemetry=True)
    for tp in (1, 2):
        on, n_on = _generate(depth=0, telemetry=True, tp=tp)
        off, n_off = _generate(depth=0, telemetry=False, tp=tp)
        assert n_on == n_off
        for a, b, c in zip(ref, on, off):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)


# --------------------------------------------------------- train regions

def test_trainstep_hlo_carries_phase_regions():
    """The compiled TrainStep's op_name metadata carries the
    forward/backward/optimizer group regions (``StepProfiler`` attributes
    a live trace against exactly this map)."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.models import (
        GPTConfig,
        GPTPretrainingCriterion,
    )
    from paddle_tpu.observability.program_inventory import (
        get_program_inventory,
    )

    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                    num_heads=2, max_position_embeddings=32)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    criterion = GPTPretrainingCriterion(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())

    def loss_fn(m, ids, labels):
        return criterion(m(ids), labels)

    step = TrainStep(model, loss_fn, optimizer, nonblocking=True)
    ids = np.ones((2, 8), dtype=np.int32)
    step(ids, ids.copy()).loss_value()
    entry = get_program_inventory().entries(kind="train_step")[-1]
    hlo = get_program_inventory().hlo_text(entry)
    assert hlo
    _, regions = parse_hlo_instruction_regions(hlo)
    groups = {p[0] for p in regions.values() if p}
    assert {"forward", "backward", "optimizer"} <= groups, groups


# --------------------------------------------------- profiler edge cases

def test_step_profiler_capture_error_never_raises():
    def boom():
        raise RuntimeError("step exploded")

    prof = StepProfiler(boom, lambda: [])
    out = prof.capture(steps=1)
    assert out["enabled"] is False
    assert "step exploded" in out["error"]
    assert prof.last_summary == out
    # the process-wide trace lock was released: a second capture runs
    ran = []
    prof2 = StepProfiler(lambda: ran.append(1), lambda: [])
    out2 = prof2.capture(steps=2)
    assert ran == [1, 1]
    assert out2["enabled"] is True and out2["steps_requested"] == 2
