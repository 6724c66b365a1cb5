"""Observability layer: MetricsRegistry (+ Prometheus round-trip),
deterministic-reservoir Histogram, CompileTracker recompile detection,
profiler scheduler/state-machine fixes, per-category span blocks, and the
framework-wide spans (train step / optimizer / collective / dataloader).
"""

import json
import os
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
from paddle_tpu.observability import (
    CompileTracker,
    MetricsRegistry,
    RecompileStorm,
    get_compile_tracker,
    get_registry,
    parse_prometheus_text,
)
from paddle_tpu.observability.metrics import Histogram


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """This module drives a serving workload: same fence as
    test_serving_sched — XLA:CPU AOT replay corrupts decode-program
    numerics, so compile fresh here."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


# ------------------------------------------------------------ registry

def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "desc")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)                      # counters are monotonic
    g = reg.gauge("depth")
    g.set(7)
    g.dec(2.5)
    assert g.value == 4.5
    # get-or-create returns the SAME object; kind mismatch raises
    assert reg.counter("requests_total") is c
    with pytest.raises(TypeError):
        reg.gauge("requests_total")
    snap = reg.snapshot()
    assert snap["requests_total"] == 5 and snap["depth"] == 4.5


def test_registry_namespace_and_sanitization():
    reg = MetricsRegistry(namespace="serving")
    reg.counter("ttft.p50-ms")        # invalid prometheus chars
    assert "serving_ttft_p50_ms" in reg.snapshot()


def test_histogram_reservoir_is_not_last_window_biased():
    """The old stride-reservoir overwrote slot count % max — percentiles
    reflected only the LAST window while count/mean covered the stream.
    The Algorithm-R reservoir must keep old observations represented."""
    h = Histogram(max_samples=256, seed=1)
    n = 4096 * 3
    for i in range(n):
        h.record(0.0 if i < 2 * n // 3 else 1.0)
    s = h.summary()
    assert s["count"] == n
    assert s["mean"] == pytest.approx(1.0 / 3.0, abs=1e-9)  # exact total/count
    # two-thirds of the stream is 0.0 -> the median of a uniform sample must
    # be 0.0; a last-window ring would report 1.0 here
    assert s["p50"] == 0.0
    assert s["max"] == 1.0


def test_histogram_deterministic_and_exact_stats():
    a, b = Histogram(max_samples=64, seed=7), Histogram(max_samples=64, seed=7)
    vals = list(range(1000))
    for v in vals:
        a.record(v)
        b.record(v)
    assert a.summary() == b.summary()          # fixed seed -> reproducible
    s = a.summary()
    assert s["mean"] == pytest.approx(np.mean(vals))
    assert s["max"] == 999 and a.min_seen == 0
    # reservoir is a uniform sample of the WHOLE stream: its median must sit
    # near the true median, not near the tail
    assert 250 <= s["p50"] <= 750
    assert Histogram().summary() == {"count": 0}


def test_prometheus_text_round_trip():
    reg = MetricsRegistry(namespace="t")
    reg.counter("events_total", "events").inc(41)
    reg.gauge("depth").set(2.25)
    h = reg.histogram("lat_seconds", "latency", unit="s")
    for i in range(500):
        h.record(i / 1000.0)
    parsed = parse_prometheus_text(reg.prometheus_text())
    snap = reg.snapshot()
    assert parsed["t_events_total"]["type"] == "counter"
    assert parsed["t_events_total"]["value"] == snap["t_events_total"]
    assert parsed["t_depth"]["value"] == snap["t_depth"]
    lat = parsed["t_lat_seconds"]
    assert lat["type"] == "summary"
    assert lat["count"] == 500
    assert lat["sum"] == pytest.approx(h.total)
    assert lat["quantiles"][0.5] == pytest.approx(snap["t_lat_seconds"]["p50"])
    assert lat["quantiles"][0.99] == pytest.approx(
        snap["t_lat_seconds"]["p99"])


def test_serving_metrics_registry_backed():
    from paddle_tpu.serving import ServingMetrics

    m = ServingMetrics()
    m.requests_received += 3
    m.generated_tokens += 10
    m.queue_depth = 4
    m.ttft.record(0.5)
    snap = m.snapshot()
    assert snap["requests_received"] == 3
    assert snap["generated_tokens"] == 10
    assert snap["queue_depth"] == 4
    # the same numbers ride the registry's prometheus export
    prom = parse_prometheus_text(m.prometheus_text())
    assert prom["serving_requests_received"]["value"] == 3
    assert prom["serving_ttft_seconds"]["count"] == 1
    # instances are isolated: one registry per scheduler
    m2 = ServingMetrics()
    assert m2.requests_received == 0


# ------------------------------------------------------- compile tracker

def test_compile_tracker_records_and_storms():
    tracker = CompileTracker(registry=MetricsRegistry(namespace="tt"))
    tracker.record("fn_a", 0.1, ("float32[2,2]",))
    assert tracker.compiles("fn_a") == 1
    assert tracker.steady_state_recompiles("fn_a") == 0
    tracker.mark_steady("fn_a")
    with pytest.warns(RecompileStorm, match="recompile storm"):
        tracker.record("fn_a", 0.2, ("float32[3,3]",))
    assert tracker.steady_state_recompiles("fn_a") == 1
    ev = tracker.events_for("fn_a")[-1]
    assert ev.steady_state and "float32[3,3]" in ev.signature
    snap = tracker.snapshot()
    assert snap["compiles_total"] == 2
    assert snap["steady_state_recompiles_total"] == 1
    assert tracker.registry.snapshot()["tt_compiles_total"] == 2


def test_compile_tracker_detects_induced_recompile_on_jitted_fn():
    """A shape change on a warmed-up @to_static function must surface as a
    tracked compile with the triggering abstract signature, and as a loud
    RecompileStorm once the function is steady-state."""
    tracker = get_compile_tracker()

    @paddle.jit.to_static
    def f(x):
        return x * 2 + 1

    name = f._tracker_name
    x22 = paddle.to_tensor(np.zeros((2, 2), np.float32))
    f(x22)
    assert tracker.compiles(name) == 1
    ev = tracker.events_for(name)[0]
    assert ev.wall_s > 0 and "float32[2,2]" in ev.signature
    f(x22)                                    # cache hit: no growth
    f(paddle.to_tensor(np.ones((2, 2), np.float32)))
    assert tracker.compiles(name) == 1
    tracker.mark_steady(name)
    with pytest.warns(RecompileStorm):
        f(paddle.to_tensor(np.zeros((3, 3), np.float32)))
    assert tracker.steady_state_recompiles(name) == 1
    assert "float32[3,3]" in tracker.events_for(name)[-1].signature
    # the process-wide registry carries the totals
    snap = get_registry().snapshot()
    assert snap["compiles_total"] >= 2
    assert snap["steady_state_recompiles_total"] >= 1
    assert snap["compile_seconds"]["count"] >= 2


def test_train_step_reports_compiles_and_span():
    """TrainStep is a tracked jit entry: its first call registers compiles,
    steady-state calls register none, and each call emits a train.step span
    in the ProfileStep category."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.nn import Linear

    tracker = get_compile_tracker()
    model = Linear(4, 4)
    o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
    step = TrainStep(model, lambda m, x: paddle.mean(m(x) * m(x)), o)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    with prof.Profiler(timer_only=False) as p:
        step(x)
        n_warm = tracker.compiles(step._tracker_name)
        step(x)
    assert n_warm >= 1
    assert tracker.compiles(step._tracker_name) == n_warm  # steady: no growth
    report = p.summary()
    assert "train.step" in report
    assert "[ProfileStep] spans" in report


# ------------------------------------------------------------- profiler

def test_make_scheduler_phase_boundaries_with_skip_first_and_repeat():
    s = prof.make_scheduler(closed=2, ready=1, record=2, repeat=2,
                            skip_first=3)
    states = [s(i) for i in range(15)]
    C, R, REC, RAR = (prof.ProfilerState.CLOSED, prof.ProfilerState.READY,
                      prof.ProfilerState.RECORD,
                      prof.ProfilerState.RECORD_AND_RETURN)
    assert states[:3] == [C, C, C]                    # skip_first
    assert states[3:8] == [C, C, R, REC, RAR]         # cycle 1
    assert states[8:13] == [C, C, R, REC, RAR]        # cycle 2
    assert states[13:] == [C, C]                      # repeat exhausted


def test_profiler_record_to_ready_snapshots(tmp_path):
    """Exiting RECORD to READY (not only to CLOSED) must snapshot: the old
    state machine silently dropped the recorded window."""
    handler_calls = []

    def scheduler(step):
        return (prof.ProfilerState.RECORD if step < 2
                else prof.ProfilerState.READY)

    p = prof.Profiler(scheduler=scheduler,
                      on_trace_ready=lambda pr: handler_calls.append(
                          len(pr._last_events)))
    p.start()
    for i in range(3):
        with prof.RecordEvent("win", prof.TracerEventType.Forward):
            time.sleep(0.001)
        p.step()
    assert handler_calls and handler_calls[0] >= 2, \
        "RECORD->READY dropped the recorded events"
    names = {e["name"] for e in p._last_events}
    assert "win" in names
    p.stop()


def test_export_chrome_tracing_unique_filenames_within_one_second(tmp_path):
    paths = []
    for _ in range(2):
        with prof.Profiler(on_trace_ready=prof.export_chrome_tracing(
                str(tmp_path), worker_name="w"), timer_only=False) as p:
            with prof.RecordEvent("e"):
                pass
        paths.append(p._exported_path)
    assert paths[0] != paths[1]
    assert all(os.path.exists(x) for x in paths)


def test_chrome_trace_round_trip_via_load_profiler_result(tmp_path):
    with prof.Profiler(timer_only=False) as p:
        with prof.RecordEvent("alpha", prof.TracerEventType.Forward):
            time.sleep(0.001)
        with prof.RecordEvent("beta", prof.TracerEventType.Backward):
            time.sleep(0.001)
    path = str(tmp_path / "trace.json")
    p.export(path)
    loaded = prof.load_profiler_result(path)
    by_name = {e["name"]: e for e in loaded["traceEvents"]}
    assert set(by_name) >= {"alpha", "beta"}
    assert by_name["alpha"]["cat"] == "Forward"
    assert by_name["beta"]["cat"] == "Backward"
    assert by_name["alpha"]["dur"] > 0


def test_summary_renders_per_category_blocks():
    with prof.Profiler(timer_only=False) as p:
        with prof.RecordEvent("fwd", prof.TracerEventType.Forward):
            pass
        with prof.RecordEvent("comm.x", prof.TracerEventType.Communication):
            pass
        with prof.RecordEvent("load", prof.TracerEventType.Dataloader):
            pass
    report = p.summary()
    assert "[Forward] spans" in report
    assert "[Communication] spans" in report
    assert "[Dataloader] spans" in report


def test_export_report_merges_spans_and_metrics(tmp_path):
    get_registry().counter("report_probe_total").inc(3)
    extra = MetricsRegistry(namespace="extra")
    extra.gauge("knob").set(1.5)
    with prof.Profiler(timer_only=False) as p:
        with prof.RecordEvent("fwd", prof.TracerEventType.Forward):
            time.sleep(0.001)
    path = str(tmp_path / "report.json")
    rep = p.export_report(path, registries=[extra])
    on_disk = json.loads(open(path).read())
    for r in (rep, on_disk):
        assert r["spans"]["fwd"]["calls"] == 1
        assert "Forward" in r["categories"]
        assert r["metrics"]["default"]["report_probe_total"] >= 3
        assert r["metrics"]["extra"]["extra_knob"] == 1.5
        assert "compiles_total" in r["compiles"]


# ------------------------------------------------- framework-wide spans

def test_optimizer_step_span():
    import paddle_tpu.optimizer as opt
    from paddle_tpu.nn import Linear

    model = Linear(3, 3)
    o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
    with prof.Profiler(timer_only=False) as p:
        loss = paddle.mean(model(paddle.to_tensor(
            np.ones((2, 3), np.float32))))
        loss.backward()
        o.step()
    report = p.summary()
    assert "optimizer.step" in report
    assert "[Optimization] spans" in report


def test_collective_span():
    import paddle_tpu.distributed as dist

    with prof.Profiler(timer_only=False) as p:
        dist.barrier()
    report = p.summary()
    assert "comm.barrier" in report
    assert "[Communication] spans" in report


def test_dataloader_span():
    from paddle_tpu.io import DataLoader, Dataset

    class DS(Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return np.full((2,), i, np.float32)

    with prof.Profiler(timer_only=False) as p:
        batches = list(DataLoader(DS(), batch_size=4))
    assert len(batches) == 2
    report = p.summary()
    assert "dataloader.next" in report
    assert "[Dataloader] spans" in report

# ------------------------------------ label escaping + cardinality guard

def test_hostile_label_values_round_trip():
    """Label values containing ``"``, ``\\``, and newlines must survive the
    exposition round-trip byte-exact — escape on write, unescape on parse
    (regression: the old unescape corrupted combined escapes and a raw
    newline split the exposition line)."""
    from paddle_tpu.observability.metrics import label_string

    hostile = [
        'plain',
        'has "quotes" inside',
        'back\\slash',
        'trailing backslash\\',
        'line\nbreak',
        '\\"combined\\" escapes',
        '\\n literal-backslash-n',
        'all three: "q" \\b\\ and\nnewline',
    ]
    reg = MetricsRegistry(namespace="h")
    c = reg.counter("hostile_total", "hostile label values")
    for i, v in enumerate(hostile):
        c.labels(value=v).inc(i + 1)
    text = reg.prometheus_text()
    # the exposition stays line-structured: one series line per value
    assert len([ln for ln in text.splitlines()
                if ln.startswith("h_hostile_total{")]) == len(hostile)
    parsed = parse_prometheus_text(text)
    got = {labels["value"]: val
           for labels, val in parsed["h_hostile_total"]["labeled"]}
    assert got == {v: float(i + 1) for i, v in enumerate(hostile)}
    # snapshot keys stay canonical + parse back to the same values
    snap = reg.snapshot()
    for i, v in enumerate(hostile):
        key = f"h_hostile_total{{{label_string({'value': v})}}}"
        assert snap[key] == float(i + 1)


def test_label_cardinality_cap_both_sides():
    """Below the cap every label set gets its own series; past it new sets
    collapse into the ``overflow="true"`` sink with a counted drop and ONE
    loud warning — and previously-seen sets still resolve to their own
    children."""
    from paddle_tpu.observability.metrics import MetricsCardinalityOverflow

    reg = MetricsRegistry(namespace="cap")
    c = reg.counter("shards_total", "per-shard events")
    c.max_label_sets = 8

    # below the cap: distinct children, no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for i in range(8):
            c.labels(shard=str(i)).inc()
    assert c.overflow_dropped == 0
    assert c.labels(shard="3") is c.labels(shard="3")

    # past the cap: the sink absorbs NEW sets, one warning total
    with pytest.warns(MetricsCardinalityOverflow):
        over1 = c.labels(shard="8")
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # second overflow: silent
        over2 = c.labels(shard="9")
        # known sets still hit their own child, not the sink
        assert c.labels(shard="5") is not over1
    assert over1 is over2                    # one shared sink child
    over1.inc(5)
    assert c.overflow_dropped == 2

    snap = reg.snapshot()
    assert snap['cap_shards_total{overflow="true"}'] == 5.0
    assert snap['cap_shards_total{shard="3"}'] == 1.0
    assert 'cap_shards_total{shard="9"}' not in snap
    # the sink rides the normal exposition too
    parsed = parse_prometheus_text(reg.prometheus_text())
    series = parsed["cap_shards_total"]["series"]
    assert series['overflow="true"'] == 5.0
    assert len(series) == 9                  # 8 real + 1 sink


def test_gauge_cardinality_cap():
    """The guard covers Gauge families too (shared _Labeled machinery)."""
    from paddle_tpu.observability.metrics import MetricsCardinalityOverflow

    reg = MetricsRegistry(namespace="g")
    g = reg.gauge("depth")
    g.max_label_sets = 2
    g.labels(q="a").set(1)
    g.labels(q="b").set(2)
    with pytest.warns(MetricsCardinalityOverflow):
        g.labels(q="c").set(7)
    snap = reg.snapshot()
    assert snap['g_depth{overflow="true"}'] == 7.0
    assert g.overflow_dropped == 1
