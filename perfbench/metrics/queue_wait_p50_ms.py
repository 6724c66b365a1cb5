"""Median time from when a scored request was due to the start of its
``admit`` phase in the scheduler's request tracer (same host clock)."""
from perfbench.harness import serve_view as view
from perfbench.harness.stats import percentile

UNIT, SOURCE = "ms", "program_span"


def read(rec):
    if rec["kind"] != "serve" or rec["closed_loop"]:
        return None
    return percentile([(r.admit_s - r.due_s) * 1e3
                       for r in view.scored(rec)
                       if r.admit_s is not None], 50)
