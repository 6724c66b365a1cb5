"""Request-lifecycle observability: per-request tracing, labeled metrics,
serving host-stall attribution, flight recorder + alarms, SLO/goodput
accounting, and the live /metrics + /debug/requests endpoint.

Correctness bar: phase durations partition E2E latency EXACTLY (gapless
same-timestamp transitions), and the token stream is bit-identical with
observability on vs off (tracing observes the host timeline, never the
model).
"""

import json
import time
import urllib.request
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (
    FlightRecorder,
    MetricsRegistry,
    ObservabilityEndpoint,
    RequestTracer,
    ServingStall,
    TTFTBreachStorm,
    parse_prometheus_text,
)
from paddle_tpu.observability.request_trace import (
    PHASE_ADMIT,
    PHASE_PREEMPTED,
    PHASE_QUEUED,
    PHASE_RUNNING,
)
from paddle_tpu.observability.serving_stall import (
    AlarmMonitors,
    EvictionThrash,
    STALL_PHASES,
)


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """Serving decode programs compile fresh (XLA:CPU AOT replay corrupts
    their numerics — same guard as test_serving_sched)."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(7)
    return GPTForCausalLM(gpt_tiny(num_layers=1))


# ------------------------------------------------------- labeled metrics

def test_counter_gauge_labels_exposition_round_trip():
    reg = MetricsRegistry(namespace="t")
    fam = reg.counter("stall_seconds", "stall by phase")
    fam.labels(phase="admission").inc(0.25)
    fam.labels(phase="streaming").inc(0.5)
    # same label set -> the SAME child
    fam.labels(phase="admission").inc(0.25)
    g = reg.gauge("depth")
    g.labels(queue="high").set(3)
    text = reg.prometheus_text()
    assert 't_stall_seconds{phase="admission"} 0.5' in text
    assert 't_stall_seconds{phase="streaming"} 0.5' in text
    assert 't_depth{queue="high"} 3' in text
    parsed = parse_prometheus_text(text)
    assert parsed["t_stall_seconds"]["series"] == {
        'phase="admission"': 0.5, 'phase="streaming"': 0.5}
    assert ({"phase": "admission"}, 0.5) in parsed["t_stall_seconds"][
        "labeled"]
    # snapshot carries labeled children under name{k="v"} keys
    snap = reg.snapshot()
    assert snap['t_stall_seconds{phase="admission"}'] == 0.5
    # untouched parent of a labeled family is suppressed from exposition
    assert "\nt_stall_seconds 0" not in text
    # children are counters too: monotonic
    with pytest.raises(ValueError):
        fam.labels(phase="admission").inc(-1)
    with pytest.raises(ValueError):
        fam.labels(phase="admission").labels(x="y")   # no nested labels


def test_unlabeled_metrics_exposition_unchanged():
    reg = MetricsRegistry()
    reg.counter("events_total").inc(3)
    text = reg.prometheus_text()
    assert "events_total 3" in text
    assert parse_prometheus_text(text)["events_total"]["value"] == 3


# -------------------------------------------------------- request traces

def test_request_trace_phases_partition_e2e_exactly():
    tracer = RequestTracer()
    tr = tracer.start(7, t=100.0, prompt_tokens=5)
    tr.transition(PHASE_ADMIT, t=100.5)
    tr.subspan("prefill", 0.2)          # nested: excluded from partition
    tr.transition(PHASE_RUNNING, t=101.0)
    tr.transition(PHASE_PREEMPTED, t=101.25)
    tr.transition(PHASE_ADMIT, t=101.5)
    tr.transition(PHASE_RUNNING, t=102.0)
    tracer.finish(7, t=103.0)
    tr = tracer.completed()[0]
    d = tr.phase_durations()
    assert d == {PHASE_QUEUED: 0.5, PHASE_ADMIT: 1.0,
                 PHASE_RUNNING: 1.25, PHASE_PREEMPTED: 0.25}
    assert sum(d.values()) == pytest.approx(tr.e2e_s())
    assert tr.e2e_s() == 3.0
    assert tr.phase_count(PHASE_ADMIT) == 2
    dd = tr.to_dict()
    assert dd["subspans"]["prefill"] == {"calls": 1, "total_s": 0.2}
    assert dd["request_id"] == 7 and dd["prompt_tokens"] == 5


def test_tracer_ring_bound_and_disabled_noop():
    tracer = RequestTracer(max_completed=2)
    for rid in range(4):
        tracer.start(rid)
        tracer.finish(rid)
    assert [t.request_id for t in tracer.completed()] == [2, 3]
    off = RequestTracer(enabled=False)
    assert off.start(0) is None and off.get(0) is None
    off.finish(0)                        # harmless
    assert off.to_json() == []


def test_chrome_trace_one_track_per_request():
    tracer = RequestTracer()
    for rid in (3, 9):
        tr = tracer.start(rid, t=0.0)
        tr.transition(PHASE_ADMIT, t=0.1)
        tr.event("resumed", t=0.15)
        tr.transition(PHASE_RUNNING, t=0.2)
        tracer.finish(rid, t=0.3)
    ct = tracer.chrome_trace()
    by_tid = {}
    for e in ct["traceEvents"]:
        if e["ph"] != "M" or e["name"] == "thread_name":
            by_tid.setdefault(e["tid"], []).append(e)
    assert set(by_tid) == {3, 9}
    names = {e["name"] for e in by_tid[3]}
    assert {"req.queued", "req.admit", "req.running",
            "req.resumed"} <= names
    span = next(e for e in by_tid[3] if e["name"] == "req.admit")
    assert span["ph"] == "X" and span["dur"] > 0


# ------------------------------------------------- stall + flight + alarms

def test_serving_stall_breakdown_and_prometheus_face():
    reg = MetricsRegistry(namespace="serving")
    st = ServingStall(reg)
    st.record("admission", 0.1)
    with st.timed("sampling_sync"):
        time.sleep(0.002)
    snap = st.snapshot()
    assert set(snap) == set(STALL_PHASES) | {"total"}
    assert snap["admission"] == pytest.approx(0.1)
    assert snap["sampling_sync"] >= 0.002
    assert snap["total"] == pytest.approx(
        sum(snap[p] for p in STALL_PHASES))
    assert 'serving_host_stall_seconds{phase="admission"}' \
        in reg.prometheus_text()
    with pytest.raises(KeyError):
        st.record("nope", 1.0)
    # default-registry flavor gets the serving_ prefix
    st2 = ServingStall()
    st2.record("streaming", 0.0)
    from paddle_tpu.observability import get_registry

    assert any(k.startswith("serving_host_stall_seconds")
               for k in get_registry().snapshot())


def test_flight_recorder_ring_and_alarm_freeze():
    fr = FlightRecorder(max_steps=3)
    for i in range(5):
        fr.record_step(queue_depth=i)
    dump = fr.dump()
    assert len(dump) == 3
    assert [r["step"] for r in dump] == [3, 4, 5]
    assert fr.steps_recorded == 5
    assert fr.dump(last=1)[0]["queue_depth"] == 4
    fr.alarm("test_alarm", "because")
    fr.record_step(queue_depth=9)        # ring rolls on...
    assert fr.last_alarm_dump["kind"] == "test_alarm"
    # ...but the frozen dump kept the incident window
    assert [r["step"] for r in fr.last_alarm_dump["steps"]] == [3, 4, 5]


def test_ttft_breach_storm_and_eviction_thrash_alarms():
    fr = FlightRecorder(8)
    mon = AlarmMonitors(fr, ttft_streak=3, thrash_window=4, thrash_frac=0.5)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        mon.observe_ttft(True, 0.9, 0.1)
        mon.observe_ttft(False, 0.05, 0.1)   # streak resets
        mon.observe_ttft(True, 0.9, 0.1)
        mon.observe_ttft(True, 0.9, 0.1)
        assert not any(isinstance(x.message, TTFTBreachStorm) for x in w)
        mon.observe_ttft(True, 0.9, 0.1)     # third consecutive -> storm
    assert any(isinstance(x.message, TTFTBreachStorm) for x in w)
    assert fr.last_alarm_dump["kind"] == "ttft_breach_storm"
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in range(4):
            mon.observe_evictions(2)
    assert any(isinstance(x.message, EvictionThrash) for x in w)


# --------------------------------------------------------- SLO / goodput

def _fake_req_out(ttft, tpot, n_tokens, preemptions=0):
    class Out:
        ttft_s, tpot_s = ttft, tpot
        generated_ids = np.arange(n_tokens)

    class Req:
        num_preemptions = preemptions

    return Req(), Out()


def test_slo_breach_attribution_and_goodput():
    from paddle_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics(ttft_slo_s=0.1, tpot_slo_s=0.05)
    tracer = RequestTracer()
    # queue-dominated TTFT breach
    tr = tracer.start(0, t=0.0)
    tr.transition(PHASE_ADMIT, t=0.4)        # 0.4s queued
    tr.transition(PHASE_RUNNING, t=0.45)     # 0.05s admit
    tracer.finish(0, t=0.6)
    req, out = _fake_req_out(0.45, 0.01, 10)
    v = m.observe_slo(req, out, trace=tracer.get(0))
    assert v["ttft_breach"] and v["ttft_cause"] == "queue_wait"
    assert not v["tpot_breach"]
    # prefill-dominated TTFT breach
    tr = tracer.start(1, t=0.0)
    tr.transition(PHASE_ADMIT, t=0.01)
    tr.transition(PHASE_RUNNING, t=0.3)      # 0.29s admit (prefill)
    tracer.finish(1, t=0.4)
    req, out = _fake_req_out(0.3, 0.01, 10)
    v = m.observe_slo(req, out, trace=tracer.get(1))
    assert v["ttft_breach"] and v["ttft_cause"] == "prefill"
    # TPOT breach attributed to preemption
    req, out = _fake_req_out(0.05, 0.2, 10, preemptions=1)
    v = m.observe_slo(req, out, trace=None)
    assert v["tpot_breach"] and v["tpot_cause"] == "preemption"
    # a compliant request earns goodput
    req, out = _fake_req_out(0.05, 0.01, 10)
    v = m.observe_slo(req, out)
    assert not v["ttft_breach"] and not v["tpot_breach"]
    snap = m.slo_snapshot()
    assert snap["judged_tokens"] == 40 and snap["goodput_tokens"] == 10
    assert snap["goodput_ratio"] == pytest.approx(0.25)
    assert snap["breaches"]['cause="queue_wait",kind="ttft"'] == 1
    assert snap["breaches"]['cause="prefill",kind="ttft"'] == 1
    assert snap["breaches"]['cause="preemption",kind="tpot"'] == 1
    prom = parse_prometheus_text(m.prometheus_text())
    assert prom["serving_slo_breach_total"]["series"][
        'cause="queue_wait",kind="ttft"'] == 1
    assert prom["serving_goodput_ratio"]["value"] == pytest.approx(0.25)


# ------------------------------------------- scheduler integration (e2e)

def _run(model, prompts, max_new, **cfg_kw):
    from paddle_tpu.serving import ContinuousBatchingScheduler, \
        SchedulerConfig

    cfg = SchedulerConfig(**cfg_kw)
    sched = ContinuousBatchingScheduler(model, cfg)
    outs = sched.generate(prompts, max_new_tokens=max_new)
    return sched, outs


def test_lifecycle_spans_across_preempt_resume(model):
    """Forced preemption: the victim's trace carries queued -> admit ->
    running -> preempted -> admit(resume) -> running -> done, phase
    durations sum to its measured E2E latency, and tokens are identical
    with tracing off."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 1000, 10), rng.integers(0, 1000, 9)]
    kw = dict(max_num_seqs=2, max_seq_len=64, block_size=4, num_blocks=6,
              max_new_tokens=8)
    sched, outs = _run(model, prompts, 8, enable_request_tracing=True, **kw)
    assert sched.metrics.preemptions >= 1
    traces = {t.request_id: t for t in sched.tracer.completed()}
    assert len(traces) == 2
    victim = next(t for t in traces.values()
                  if t.phase_count(PHASE_PREEMPTED) >= 1)
    phases = [p for p, _, _ in victim.phases]
    assert phases[0] == PHASE_QUEUED
    assert PHASE_PREEMPTED in phases
    assert phases.index(PHASE_PREEMPTED) < len(phases) - 1
    # resumed: a second admit AFTER the preemption
    assert victim.phase_count(PHASE_ADMIT) >= 2
    assert any(n == "resumed" for n, _, _ in victim.events)
    for tr in traces.values():
        d = tr.phase_durations()
        assert sum(d.values()) == pytest.approx(tr.e2e_s(), abs=1e-9)
        assert tr.meta["finish_reason"] in ("eos", "length")
    # token identity: tracing off produces the same streams
    sched_off, outs_off = _run(model, prompts, 8,
                               enable_request_tracing=False, **kw)
    assert sched_off.tracer.completed() == []
    for a, b in zip(outs, outs_off):
        np.testing.assert_array_equal(a, b)


def test_prefix_cache_hit_admission_traced(model):
    """A radix-tree hit shows up in the request's trace: cached_tokens
    noted, prefix_hit event, radix_match sub-span recorded."""
    from paddle_tpu.serving import ContinuousBatchingScheduler, \
        SchedulerConfig

    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 1000, 32)
    cfg = SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8,
                          enable_prefix_caching=True)
    sched = ContinuousBatchingScheduler(model, cfg)
    sched.add_request(prompt, max_new_tokens=4)
    while sched.has_unfinished():
        sched.step()
    rid2 = sched.add_request(prompt, max_new_tokens=4)   # full-prefix hit
    while sched.has_unfinished():
        sched.step()
    tr = sched.tracer.get(rid2)
    assert tr.meta["cached_tokens"] > 0
    assert tr.meta["prefilled_tokens"] + tr.meta["cached_tokens"] \
        == len(prompt)
    assert "prefix_match" in tr.subspans and "prefill" in tr.subspans
    assert sched.stall.seconds("radix_match") > 0
    d = tr.phase_durations()
    assert sum(d.values()) == pytest.approx(tr.e2e_s(), abs=1e-9)


def test_stall_breakdown_populated_by_serving(model):
    rng = np.random.default_rng(0)
    sched, _ = _run(model, [rng.integers(0, 1000, 8) for _ in range(3)], 6,
                    max_num_seqs=2, max_seq_len=64, block_size=8)
    snap = sched.stall.snapshot()
    for phase in ("admission", "block_accounting", "streaming",
                  "sampling_sync"):
        assert snap[phase] > 0, (phase, snap)
    assert snap["total"] < 1.0          # bookkeeping, not seconds of work
    # the breakdown rides the scheduler's ServingMetrics prometheus text
    prom = sched.metrics.prometheus_text()
    assert 'serving_host_stall_seconds{phase="sampling_sync"}' in prom
    # flight recorder saw every iteration
    assert sched.flight.steps_recorded > 0
    row = sched.flight.dump(last=1)[0]
    assert {"running", "queue_depth", "free_blocks", "prefill_tokens",
            "generated_tokens", "preemptions"} <= set(row)


def test_endpoint_serves_live_scheduler(model):
    rng = np.random.default_rng(5)
    from paddle_tpu.serving import ContinuousBatchingScheduler, \
        SchedulerConfig

    sched = ContinuousBatchingScheduler(model, SchedulerConfig(
        max_num_seqs=2, max_seq_len=64, block_size=8,
        ttft_slo_s=10.0, tpot_slo_s=10.0))
    for _ in range(3):
        sched.add_request(rng.integers(0, 1000, 8), max_new_tokens=4)
    ep = sched.start_endpoint()
    try:
        sched.step()                     # some live, some queued
        dbg = json.loads(urllib.request.urlopen(
            ep.url + "/debug/requests", timeout=10).read().decode())
        s0 = dbg["scheduler0"]
        states = {r["state"] for r in s0["requests"]}
        assert "RUNNING" in states and len(s0["requests"]) == 3
        assert set(s0["stall_seconds"]) == set(STALL_PHASES) | {"total"}
        while sched.has_unfinished():
            sched.step()
        text = urllib.request.urlopen(
            ep.url + "/metrics", timeout=10).read().decode()
        prom = parse_prometheus_text(text)
        assert prom["serving_requests_finished"]["value"] == 3
        assert 'serving_host_stall_seconds{phase="admission"}' in text
        assert prom["serving_goodput_ratio"]["value"] == 1.0
        # process-wide default registry rides the same page
        assert "compiles_total" in prom
        dbg = json.loads(urllib.request.urlopen(
            ep.url + "/debug/requests?last=2", timeout=10).read().decode())
        assert len(dbg["scheduler0"]["flight_recorder"]) == 2
        assert len(dbg["scheduler0"]["traces"]["completed"]) == 3
        # liveness + 404 routing
        assert urllib.request.urlopen(
            ep.url + "/healthz", timeout=10).read() == b"ok"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(ep.url + "/nope", timeout=10)
    finally:
        ep.stop()


def test_ttft_breach_storm_fires_on_scheduler(model):
    rng = np.random.default_rng(2)
    from paddle_tpu.serving import ContinuousBatchingScheduler, \
        SchedulerConfig

    sched = ContinuousBatchingScheduler(model, SchedulerConfig(
        max_num_seqs=2, max_seq_len=64, block_size=8,
        ttft_slo_s=1e-9, ttft_breach_streak=3))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in range(4):
            sched.add_request(rng.integers(0, 1000, 6), max_new_tokens=3)
        while sched.has_unfinished():
            sched.step()
    assert any(isinstance(x.message, TTFTBreachStorm) for x in w)
    assert sched.flight.last_alarm_dump["kind"] == "ttft_breach_storm"
    assert sched.metrics.slo_snapshot()["goodput_ratio"] == 0.0
    assert sum(v for v in sched.metrics.slo_snapshot()["breaches"]
               .values()) >= 4


def test_export_request_trace_chrome_artifact(model, tmp_path):
    rng = np.random.default_rng(4)
    sched, _ = _run(model, [rng.integers(0, 1000, 8)], 4,
                    max_num_seqs=2, max_seq_len=64, block_size=8)
    path = str(tmp_path / "reqtrace.json")
    sched.export_request_trace(path)
    trace = json.load(open(path))
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"req.queued", "req.admit", "req.running"} <= names
    # profiler export_report folds the same timelines in
    import paddle_tpu.profiler as prof

    with prof.Profiler(timer_only=False) as p:
        pass
    rep = p.export_report(request_tracers=[sched.tracer])
    assert rep["request_traces"][0][0]["phase_totals_s"]


# --------------------------------------------- everything on vs off

def test_full_observability_on_off_token_identity(model):
    """Everything that watches a request switched on (lifecycle tracing,
    SLO accounting, step telemetry, the device ledger, a live endpoint
    scraped between steps) against everything switched off: the same
    token streams, and the watchers did see the run."""
    from paddle_tpu.serving import ContinuousBatchingScheduler, \
        SchedulerConfig

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 1000, int(k)) for k in rng.integers(4, 11, 6)]
    budgets = [int(k) for k in rng.integers(8, 15, 6)]

    def run(on):
        sched = ContinuousBatchingScheduler(model, SchedulerConfig(
            max_num_seqs=2, max_seq_len=64, block_size=8,
            enable_request_tracing=on, enable_step_telemetry=on,
            enable_device_observability=on,
            ttft_slo_s=30.0 if on else None,
            tpot_slo_s=30.0 if on else None))
        rids = [sched.add_request(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        ep = sched.start_endpoint() if on else None
        scrapes = []
        try:
            it = 0
            while sched.has_unfinished():
                sched.step()
                it += 1
                if ep is not None and it % 4 == 0:
                    scrapes.append(urllib.request.urlopen(
                        ep.url + "/metrics", timeout=5).read().decode())
                assert it < 1000
        finally:
            if ep is not None:
                ep.stop()
        return sched, [list(sched._finished[r].token_ids) for r in rids], \
            scrapes

    off_sched, off, _ = run(False)
    on_sched, on, scrapes = run(True)
    assert on == off
    assert len(on_sched.tracer.completed()) == 6
    assert not off_sched.tracer.completed()
    assert on_sched.metrics.slo_snapshot()["judged_tokens"] == sum(budgets)
    assert on_sched.telemetry_snapshot()["steps"] > 0
    assert scrapes and "serving_generated_tokens" in scrapes[-1]


# ---------------------------------------------- live export + failover resume

def test_live_trace_export_includes_open_final_span():
    """A trace exported mid-flight (postmortem taken during an incident)
    shows the still-open phase up to "now" — not a timeline that appears
    to stop at the last transition."""
    tracer = RequestTracer()
    t0 = time.perf_counter()
    tr = tracer.start(42, t=t0 - 1.0, prompt_tokens=3)
    tr.transition(PHASE_ADMIT, t=t0 - 0.5)
    tr.transition(PHASE_RUNNING, t=t0 - 0.25)
    d = tr.to_dict()
    assert d["finish_t"] is None and d["phase"] == PHASE_RUNNING
    open_rows = [r for r in d["phases"] if r.get("open")]
    assert len(open_rows) == 1
    assert open_rows[0]["phase"] == PHASE_RUNNING
    assert open_rows[0]["t0"] == pytest.approx(t0 - 0.25)
    assert open_rows[0]["dur_s"] >= 0.25
    # the open remainder is folded into the totals, so the totals cover
    # the full arrival->now window even though the request hasn't finished
    assert sum(d["phase_totals_s"].values()) >= 1.0
    # closed rows never carry the marker
    assert all("open" not in r for r in d["phases"] if r is not open_rows[0])
    # to_json(include_live=True) carries the same synthesized row
    rows = tracer.to_json()
    assert any(r.get("open") for r in rows[-1]["phases"])
    # chrome_trace renders the live request with an open final X span
    ct = tracer.chrome_trace()
    live_spans = [e for e in ct["traceEvents"]
                  if e.get("tid") == 42 and e.get("ph") == "X"
                  and e.get("args", {}).get("open")]
    assert len(live_spans) == 1
    assert live_spans[0]["name"] == "req.running"
    assert live_spans[0]["dur"] > 0


def test_export_snapshot_resume_failover_gapless():
    """The cross-replica half of "one request = one timeline": a snapshot
    exported off a dead replica, resumed on a survivor, yields ONE trace
    whose phases still partition E2E exactly — with an explicit gapless
    ``failover`` phase bridging export -> import."""
    from paddle_tpu.observability.request_trace import PHASE_FAILOVER

    dead = RequestTracer()
    tr = dead.start(5, t=100.0, prompt_tokens=4, priority=1)
    tr.transition(PHASE_ADMIT, t=100.5)
    tr.subspan("prefill", 0.2)
    tr.transition(PHASE_RUNNING, t=101.0)
    tr.event("resumed", t=101.1)
    snap = dead.export_snapshot(5, t=101.5)
    assert snap is not None and snap["export_t"] == 101.5
    assert snap["open_phase"] == PHASE_RUNNING
    # the export REMOVED the trace from the dead tracer
    assert dead.get(5) is None and dead.live() == []

    survivor = RequestTracer()
    tr2 = survivor.resume(9, snap, t=102.0, replica_hop=1)
    assert survivor.get(9) is tr2
    # prior history survived the hop
    assert tr2.arrival_t == 100.0
    assert tr2.phase_count(PHASE_ADMIT) == 1
    assert tr2.subspans["prefill"] == [1, 0.2]
    assert any(n == "resumed" for n, _, _ in tr2.events)
    # failover phase bridges export -> import exactly
    fo = [(p, t0, t1) for p, t0, t1 in tr2.phases if p == PHASE_FAILOVER]
    assert fo == [(PHASE_FAILOVER, 101.5, 102.0)]
    # resumed request re-enters the survivor's queue
    assert tr2.current_phase == PHASE_QUEUED
    tr2.transition(PHASE_ADMIT, t=102.5)
    tr2.transition(PHASE_RUNNING, t=103.0)
    survivor.finish(9, t=104.0)
    done = survivor.completed()[0]
    d = done.phase_durations()
    assert d[PHASE_FAILOVER] == 0.5
    assert sum(d.values()) == pytest.approx(done.e2e_s(), abs=1e-9)
    assert done.e2e_s() == 4.0


def test_resume_without_snapshot_falls_back_to_start():
    survivor = RequestTracer()
    tr = survivor.resume(3, None, t=50.0, prompt_tokens=2)
    assert tr is not None and tr.arrival_t == 50.0
    assert tr.current_phase == PHASE_QUEUED
    assert tr.phase_count("failover") == 0
    off = RequestTracer(enabled=False)
    assert off.resume(3, {"arrival_t": 0.0}) is None
    assert off.export_snapshot(3) is None
