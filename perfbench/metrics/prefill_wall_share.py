"""Share of the window's wall time spent in whole-prompt prefills: the sum
of the request tracer's ``prefill`` and ``sampling_sync`` sub-spans of the
requests admitted in the window. ``prefill`` alone times only the enqueue
(``scheduler.py::_admit``); the device time is waited out in
``sampling_sync``, the blocking read of the first token, so only their sum
is the prefill. Valid while ``dispatch_depth`` is 0 and chunked prefill is
off (with either, these sub-spans stop covering the device time)."""
from perfbench.harness import serve_view as view

UNIT, SOURCE = "%", "program_span"


def read(rec):
    if rec["kind"] != "serve":
        return None
    a, b = view.scored_span(rec)
    spent = sum(r.prefill_s for r in rec["requests"]
                if r.admit_s is not None and a <= r.admit_s < b)
    return 100.0 * spent / (b - a)
