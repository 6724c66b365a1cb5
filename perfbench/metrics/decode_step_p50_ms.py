"""Median host time of the ``sched.step()`` calls that admitted nothing: one
run of the decode program over the slot grid. Valid while ``dispatch_depth``
is 0, where the call ends in the blocking read of the step's tokens."""
from perfbench.harness import serve_view as view
from perfbench.harness.stats import percentile

UNIT, SOURCE = "ms", "host_clock"


def read(rec):
    if rec["kind"] != "serve":
        return None
    p = percentile([s[1] - s[0]
                    for s in view.steps_in(rec, *view.scored_span(rec))
                    if s[2] == 0 and s[3] > 0], 50)
    return None if p is None else p * 1e3
