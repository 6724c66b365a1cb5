"""``RecordEvent`` spans in the profiler's own trace, and the phases of the
scheduler's ``step()`` among them.

One span call, two sinks: the in-process ring (while a ``Profiler``
records) and a ``jax.profiler.TraceAnnotation`` in the ``.xplane.pb`` of
whatever profiler session is open. A CPU trace holds the annotations on
``/host:CPU``, one line a thread, so the structure is checked here: names,
nesting, tokens. No time is asserted.
"""

import glob
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.profiler import Profiler, RecordEvent
from paddle_tpu.serving import ContinuousBatchingScheduler, SchedulerConfig

# span -> the spans it may lie directly inside, for what the default path
# (no prefix cache, whole-prompt prefill, dispatch_depth 0) reaches
PARENTS = {
    "serving.step": (),
    "serving.sweep": ("serving.step",),
    "serving.admit": ("serving.step",),
    "serving.decode_step": ("serving.step",),
    "serving.account": ("serving.step",),
    "serving.prefill": ("serving.admit",),
    "serving.block_accounting": ("serving.admit", "serving.step"),
    "serving.sampling_sync": ("serving.admit", "serving.step"),
    "serving.commit": ("serving.admit", "serving.step"),
    "serving.stage": ("serving.prefill", "serving.decode_step"),
    "serving.launch": ("serving.prefill", "serving.decode_step"),
}


@pytest.fixture(autouse=True, scope="module")
def _no_aot_replay():
    """As tests/test_serving_sched.py: a replayed XLA:CPU executable has
    given wrong tokens, so serving tests compile fresh."""
    import jax

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _host_events(trace_dir):
    """``{thread line: [(name, start_ns, end_ns)]}`` of the one trace under
    a ``jax.profiler`` log directory."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    return {line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events] for line in host.lines}


def _spans(trace_dir, prefix):
    return [e for evs in _host_events(trace_dir).values() for e in evs
            if e[0].startswith(prefix)]


def _serve(sched):
    """Two requests, stepped to the end: the first step admits both."""
    rng = np.random.default_rng(5)
    rids = [sched.add_request(rng.integers(0, 1000, n), max_new_tokens=4)
            for n in (6, 9)]
    outs = {}
    while sched.has_unfinished():
        for out in sched.step():
            outs[out.request_id] = list(map(int, out.generated_ids))
    return [outs[r] for r in rids]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A few steps of a ``gpt_tiny`` scheduler under a recording
    ``Profiler``: its trace directory, its ring, the tokens, and the tokens
    of the same requests through an untraced scheduler."""
    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny(num_layers=2))
    cfg = SchedulerConfig(max_num_seqs=2, max_seq_len=64, block_size=8)
    plain = _serve(ContinuousBatchingScheduler(model, cfg))
    sched = ContinuousBatchingScheduler(model, cfg)
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    with Profiler(device_trace_dir=trace_dir) as prof:
        tokens = _serve(sched)
    assert prof.device_trace_dir == trace_dir
    return {"dir": trace_dir, "ring": prof._last_events, "tokens": tokens,
            "plain": plain}


@pytest.mark.parametrize("session", ["profiler", "jax_session", "none"])
def test_record_event_reaches_each_open_sink(session, tmp_path):
    import jax

    from paddle_tpu.profiler import _recorder

    def emit():
        with RecordEvent("test.outer"):
            ev = RecordEvent("test.inner")
            ev.begin()
            ev.end()
            ev.end()                     # a second end() is a no-op

    if session == "profiler":
        with Profiler(device_trace_dir=str(tmp_path)) as prof:
            emit()
        ring = [e["name"] for e in prof._last_events]
    else:
        if session == "jax_session":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            emit()
        finally:
            if session == "jax_session":
                jax.profiler.stop_trace()
        ring = [e["name"] for e in _recorder.drain()]
    assert ring == (["test.inner", "test.outer"] if session == "profiler"
                    else [])
    if session == "none":
        assert not os.listdir(tmp_path)
        return
    (outer,), (inner,) = (_spans(str(tmp_path), n)
                          for n in ("test.outer", "test.inner"))
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]


def test_profiler_makes_its_own_trace_directory_when_given_none():
    with Profiler() as prof:
        with RecordEvent("test.default_dir"):
            pass
    assert len(_spans(prof.device_trace_dir, "test.default_dir")) == 1
    assert Profiler(timer_only=True).device_trace_dir is None


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_phase_of_step_is_in_the_trace_and_in_the_ring(traced, name):
    assert any(e[0] == name for e in _spans(traced["dir"], "serving."))
    assert any(e["name"] == name for e in traced["ring"])


def test_phases_nest_and_siblings_do_not_overlap(traced):
    by_thread = {k: [e for e in v if e[0].startswith("serving.")]
                 for k, v in _host_events(traced["dir"]).items()}
    (spans,) = [v for v in by_thread.values() if v]    # one thread steps
    assert {e[0] for e in spans} == set(PARENTS)
    spans.sort(key=lambda e: (e[1], -e[2]))
    stack, steps = [], 0
    for name, s, e in spans:
        while stack and stack[-1][2] <= s:
            stack.pop()                  # that sibling ended before this one
        if stack:
            # inside the span that is open: wholly, and under a parent the
            # table allows
            assert e <= stack[-1][2], (name, stack[-1][0])
            assert stack[-1][0] in PARENTS[name], (name, stack[-1][0])
        else:
            assert name == "serving.step"
            steps += 1
        stack.append((name, s, e))
    # 4 tokens each: the first step admits and decodes, two more decode
    assert steps == 3


def test_tracing_changes_no_token(traced):
    assert traced["tokens"] == traced["plain"]
    assert all(len(t) == 4 for t in traced["tokens"])
