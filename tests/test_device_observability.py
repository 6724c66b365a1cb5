"""Device-side observability (PR 12): DeviceMemoryLedger owner census,
OOM forensics drill, ProgramInventory + roofline arithmetic, the
``/debug`` endpoint family — and the load-bearing invariant that
switching observability on/off never changes a generated token at any
dispatch depth.
"""

import gc
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.kv_cache import KVPoolExhausted
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.observability.device_memory import (
    DeviceMemoryLedger,
    get_device_ledger,
    tree_nbytes,
)
from paddle_tpu.observability.program_inventory import (
    chip_specs,
    get_program_inventory,
    roofline_utilization,
)
from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """Serving decode programs must compile fresh: XLA:CPU AOT replay
    corrupts their numerics (same fence as test_serving_sched)."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return GPTForCausalLM(gpt_tiny(num_layers=2))


PROMPTS = (np.array([5, 6, 7, 8], dtype=np.int64),
           np.array([9, 10, 11], dtype=np.int64))


def _make_sched(model, **cfg_kw):
    kw = dict(max_num_seqs=2, max_seq_len=32, block_size=8,
              max_new_tokens=8, enable_device_observability=True)
    kw.update(cfg_kw)
    return ContinuousBatchingScheduler(model, SchedulerConfig(**kw))


@pytest.fixture(scope="module")
def served_sched(model):
    """One scheduler that has served a steady-state workload — shared by
    the census / inventory / endpoint tests (compiles are the expensive
    part of this module)."""
    sched = _make_sched(model)
    for p in PROMPTS:
        sched.add_request(p)
    outs = sched.run()
    yield sched, outs
    sched.shutdown()


# ------------------------------------------------------------- tree_nbytes

def test_tree_nbytes_counts_arrays_and_skips_scalars():
    import jax.numpy as jnp

    t = {
        "np": np.zeros((4, 4), dtype=np.float32),        # 64
        "jax": jnp.zeros((8,), dtype=jnp.float32),       # 32
        "tensor": paddle.to_tensor(np.ones((2, 3), dtype=np.float32)),  # 24
        "none": None,
    }
    assert tree_nbytes(t) == 64 + 32 + 24
    assert tree_nbytes([]) == 0
    # donated/deleted jax shells still size from the aval
    donated = jnp.zeros((16,), dtype=np.float32)
    donated.delete()
    assert tree_nbytes([donated]) == 64


# ------------------------------------------------------------------ ledger

def test_ledger_register_resize_release_watermark():
    reg = MetricsRegistry()
    led = DeviceMemoryLedger(registry=reg)
    h1 = led.register("kv_pool", "pool0", 1000)
    h2 = led.register("model_weights", "m", 500)
    assert led.live_bytes() == 1500
    assert led.live_bytes("kv_pool") == 1000
    h1.resize(2000)
    assert led.live_bytes("kv_pool") == 2000
    assert led.watermark_bytes("kv_pool") == 2000
    h1.resize(100)
    assert led.live_bytes("kv_pool") == 100
    assert led.watermark_bytes("kv_pool") == 2000   # watermark sticks
    h1.release()
    h1.release()                                    # idempotent
    h1.resize(9999)                                 # post-release no-op
    assert led.live_bytes("kv_pool") == 0
    assert led.live_bytes() == 500
    # gauges export per-owner
    g = reg.gauge("device_memory_bytes")
    assert g.labels(owner="model_weights").value == 500
    assert g.labels(owner="kv_pool").value == 0
    h2.release()


def test_ledger_overlay_excluded_from_primary_sum():
    led = DeviceMemoryLedger()
    led.register("kv_pool", "pool0", 4096)
    led.register("prefix_cache_pinned", "prefix", 1024, overlay=True)
    rep = led.census_report()
    assert rep["total_bytes"] == 4096                 # overlay excluded
    assert rep["total_bytes_with_overlays"] == 4096 + 1024
    assert rep["owners"]["prefix_cache_pinned"]["overlay"] is True
    assert rep["owners"]["kv_pool"]["overlay"] is False
    assert led.live_bytes() == 4096
    assert led.live_bytes(include_overlays=True) == 5120


def test_ledger_oom_forensics_stamps_exception():
    led = DeviceMemoryLedger()
    led.register("kv_pool", "pool0", 2048)
    exc = KVPoolExhausted("out of blocks")
    rep = led.attach_forensics(exc, flight_tail=[{"kind": "decode"}])
    assert exc.device_memory_census is rep
    assert rep["census"]["kv_pool"]["bytes"] == 2048
    assert rep["flight_recorder_tail"] == [{"kind": "decode"}]
    assert "KVPoolExhausted" in rep["reason"]
    assert led.last_oom is rep
    assert led.census_report()["last_oom"] is rep


# ------------------------------------------------------ roofline arithmetic

def test_chip_specs_one_sourced_table(monkeypatch):
    """One table keyed by device_kind exactly as JAX reports it, each row
    with its source; no v5e default, no environment override, no CPU row."""
    v5e = chip_specs("TPU v5 lite")
    assert (v5e["peak_tflops"], v5e["peak_membw_gbs"]) == (197.0, 819.0)
    assert "Google Cloud" in v5e["source"]
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "123.0")
    monkeypatch.setenv("BENCH_PEAK_MEMBW_GBS", "456.0")
    assert chip_specs("TPU v5 lite") == v5e          # env cannot override
    for unknown in ("TPU v9 imaginary", "tpu v5e", "A100", ""):
        with pytest.raises(KeyError, match="no peak specs"):
            chip_specs(unknown)


def test_chip_specs_cpu_has_no_peaks():
    """On CPU the utilisation gauges are absent rather than computed."""
    assert chip_specs() is None                      # this tier runs on CPU
    assert roofline_utilization(1e12, 1e9, 2.0) is None


def test_roofline_utilization_math_and_clamp():
    specs = {"device_kind": "x", "peak_tflops": 1.0, "peak_membw_gbs": 1.0}
    # 1e12 FLOPs in 2s on a 1-TFLOPs chip -> 50% MFU
    r = roofline_utilization(1e12, 1e9, 2.0, specs=specs)
    assert r["mfu"] == pytest.approx(0.5)
    assert r["bandwidth_util"] == pytest.approx(0.5)
    # over-peak clamps to 1.0 but keeps the raw ratio as the finding
    r = roofline_utilization(4e12, 8e9, 1.0, specs=specs)
    assert r["mfu"] == 1.0 and r["mfu_raw"] == pytest.approx(4.0)
    assert r["bandwidth_util"] == 1.0
    assert r["bandwidth_util_raw"] == pytest.approx(8.0)


# ----------------------------------------------- serving census ground truth

def test_scheduler_census_accounts_device_bytes(served_sched):
    """Acceptance pin: the ledger census accounts >=95% of the framework's
    device bytes against the pool+weights ground truth (here it is exact —
    both owners register from the same arrays the scheduler holds)."""
    sched, _ = served_sched
    pool_bytes = tree_nbytes(sched._pools)
    weight_bytes = tree_nbytes([p for p in sched.model.parameters()])
    ground_truth = pool_bytes + weight_bytes
    rep = sched.device_ledger.census_report()
    assert rep["owners"]["kv_pool"]["bytes"] == pool_bytes
    assert rep["owners"]["model_weights"]["bytes"] == weight_bytes
    assert 0.95 * ground_truth <= rep["total_bytes"] <= ground_truth
    # gauges mirror the census on the scheduler's own registry
    g = sched.metrics.registry.gauge("device_memory_bytes")
    assert g.labels(owner="kv_pool").value == pool_bytes
    assert sched.metrics.registry.gauge("kv_bytes_per_token").value > 0


def test_program_inventory_lists_serving_programs(served_sched):
    """Every steady-state serving executable shows up with nonzero XLA
    FLOPs/bytes, and AOT analysis must not grow the runtime jit cache."""
    sched, _ = served_sched
    inv = get_program_inventory()
    mine = inv.entries(name_contains=sched._step_fn.tracker_name)
    assert len(mine) >= 2            # at least one prefill + one decode
    n_before = sched.num_programs()
    for e in mine:
        an = inv.analyze(e)
        assert "error" not in an, an
        assert an["flops"] > 0
        assert an["bytes_accessed"] > 0
        assert an["peak_temp_bytes"] >= 0
    assert sched.num_programs() == n_before   # zero steady-state recompiles


def test_device_observability_report(served_sched):
    """What ``enable_device_observability`` leaves on a scheduler: the
    memory census and the inventory's cost analysis of the decode program,
    and no device time or utilisation derived from host stamps."""
    sched, _ = served_sched
    assert sched.device_ledger.census_report()["total_bytes"] > 0
    decode = [e for e in get_program_inventory().entries(
                  name_contains=sched._step_fn.tracker_name)
              if f"i32[{sched.config.max_num_seqs},1]" in e.signature]
    assert len(decode) == 1
    assert get_program_inventory().analyze(decode[0])["flops"] > 0
    prom = sched.metrics.prometheus_text()
    assert "kv_bytes_per_token" in prom
    for gone in ("decode_device_step_seconds", "decode_bandwidth_util"):
        assert gone not in prom
    off = _make_sched(sched.model, enable_device_observability=False)
    assert off.device_ledger is None
    off.shutdown()


# ----------------------------------------------------- /debug endpoint e2e

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


def test_debug_endpoints_e2e(served_sched):
    sched, _ = served_sched
    # analyze this scheduler's entries up front (results are cached): the
    # process-wide inventory may hold dozens of un-analyzed programs from
    # earlier test modules, and analyzing ALL of them inside one request
    # would make this an (order-dependent) slow test
    inv = get_program_inventory()
    for e in inv.entries(name_contains=sched._step_fn.tracker_name):
        inv.analyze(e)
    ep = sched.start_endpoint()
    try:
        # /debug index lists every registered route
        idx = _get(f"{ep.url}/debug")["routes"]
        for route in ("/metrics", "/debug", "/debug/requests",
                      "/debug/programs", "/debug/memory", "/healthz"):
            assert route in idx
        # /debug/programs (?analyze=0 keeps cached analyses): this
        # scheduler's steady-state executables are all present with
        # nonzero cost analysis
        progs = _get(f"{ep.url}/debug/programs?analyze=0")
        mine = [p for p in progs["programs"]
                if sched._step_fn.tracker_name in p["name"]]
        assert len(mine) >= 2
        for p in mine:
            assert p["analysis"]["flops"] > 0
            assert p["analysis"]["bytes_accessed"] > 0
        assert progs["count"] == len(progs["programs"]) >= len(mine)
        # /debug/memory: process-default + per-scheduler censuses
        mem = _get(f"{ep.url}/debug/memory")
        assert "default" in mem
        sched_keys = [k for k in mem if k.startswith("scheduler")]
        assert sched_keys
        owners = mem[sched_keys[0]]["owners"]
        assert owners["kv_pool"]["bytes"] > 0
        assert owners["model_weights"]["bytes"] > 0
        # unknown route 404s with the route list
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{ep.url}/debug/nope")
        assert ei.value.code == 404
    finally:
        ep.stop()


# ------------------------------------------------------- OOM forensics drill

def test_oom_forensics_drill_zero_leaks(model):
    """Tiny pool, preemption off: decode extension exhausts the pool; the
    raised KVPoolExhausted carries the owner census, and recovery leaves
    zero leaked blocks and an unchanged ledger."""
    sched = _make_sched(model, max_num_seqs=2, block_size=4, num_blocks=4,
                        max_new_tokens=8, enable_preemption=False)
    try:
        # each request fits alone (7 + 8 <= 16-token pool cap) but their
        # prompts fill all 4 blocks, so the first decode extension fails
        r1 = sched.add_request(np.arange(1, 8, dtype=np.int64))
        r2 = sched.add_request(np.arange(8, 15, dtype=np.int64))
        pool_bytes = tree_nbytes(sched._pools)
        with pytest.raises(KVPoolExhausted) as ei:
            for _ in range(64):
                sched.step()
        report = ei.value.device_memory_census
        assert report["census"]["kv_pool"]["bytes"] == pool_bytes
        assert isinstance(report["flight_recorder_tail"], list)
        assert sched.device_ledger.last_oom is report
        # recovery: cancel both requests -> every block returns to the
        # allocator and the ledger still accounts the static pool
        for rid in (r1, r2):
            sched.cancel(rid)
        assert sched.allocator.num_used_blocks == 0
        assert sched.allocator.num_free_blocks == sched.allocator.num_blocks
        assert sched.device_ledger.live_bytes("kv_pool") == pool_bytes
    finally:
        sched.shutdown()


# ------------------------------------------- the bit-identity invariant

def test_tokens_identical_obs_on_off_across_depths(model):
    """Device observability is pure host bookkeeping: generated tokens are
    bit-identical with it on vs off, at dispatch_depth 0 and 2."""
    def run(depth, obs):
        sched = _make_sched(model, dispatch_depth=depth,
                            enable_device_observability=obs)
        for p in PROMPTS:
            sched.add_request(p)
        outs = sched.run()
        toks = {rid: np.asarray(o.generated_ids).copy()
                for rid, o in outs.items()}
        sched.shutdown()
        return toks

    for depth in (0, 2):
        on, off = run(depth, True), run(depth, False)
        assert sorted(on) == sorted(off)
        for rid in on:
            np.testing.assert_array_equal(on[rid], off[rid])


# ------------------------------------------------------- train-side owners

def test_trainstep_registers_and_releases_ledger_bytes():
    from paddle_tpu.jit import TrainStep

    led = get_device_ledger()
    # flush cyclic garbage first: earlier modules' dead TrainSteps would
    # otherwise release THEIR ledger bytes during this test's gc.collect()
    # and shift the baseline mid-assertion
    inv = get_program_inventory()
    for e in inv.entries(kind="train_step"):
        inv.analyze(e)           # drops the jitted refs that pin them
    gc.collect()
    base_w = led.live_bytes("model_weights")
    base_s = led.live_bytes("optimizer_slots")

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    optimizer = opt.AdamW(learning_rate=1e-2,
                          parameters=model.parameters())
    mse = nn.MSELoss()
    step = TrainStep(model, lambda m, a, b: mse(m(a), b), optimizer)
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.random.RandomState(1)
                         .randn(4, 1).astype(np.float32))
    step(x, y)
    w_bytes = tree_nbytes([p for p in model.parameters()])
    assert led.live_bytes("model_weights") == base_w + w_bytes
    assert led.live_bytes("optimizer_slots") > base_s   # adam m+v slots
    # the inventory entry holds the jitted callable (hence the TrainStep,
    # through the bound-method cycle) until analysis drops it
    inv = get_program_inventory()
    for e in inv.entries(kind="train_step"):
        inv.analyze(e)
    del step
    gc.collect()
    assert led.live_bytes("model_weights") == base_w
    assert led.live_bytes("optimizer_slots") == base_s


def test_prefetcher_accounts_buffers():
    from paddle_tpu.io.dataloader import DevicePrefetcher

    led = get_device_ledger()
    base = led.live_bytes("prefetch_buffers")
    batches = [np.full((8, 8), i, dtype=np.float32) for i in range(4)]
    pf = DevicePrefetcher(batches, depth=1)
    seen_live = 0
    n = 0
    for out in pf:
        n += 1
        seen_live = max(seen_live, led.live_bytes("prefetch_buffers") - base)
    assert n == 4
    # depth+1 buffers of 256B each were accounted while iterating...
    assert seen_live == 2 * 8 * 8 * 4
    # ...and released once the iterator finished
    assert led.live_bytes("prefetch_buffers") == base


def test_checkpoint_staging_registered_and_released(tmp_path):
    from paddle_tpu.checkpoint import CheckpointManager

    led = get_device_ledger()
    base = led.live_bytes("checkpoint_staging")
    wm_before = led.watermark_bytes("checkpoint_staging")
    mgr = CheckpointManager(str(tmp_path))
    # the ledger is the process's: stage more than whatever an earlier
    # module of this worker left as the watermark
    mgr.save(1, state={"w": np.zeros(wm_before // 4 + 1024,
                                     dtype=np.float32)})
    # staged bytes were accounted during the write and fully returned
    assert led.watermark_bytes("checkpoint_staging") > wm_before
    assert led.live_bytes("checkpoint_staging") == base
