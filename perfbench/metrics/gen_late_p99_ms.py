"""How late the generator ran: 99th percentile of (time ``add_request``
returned - time the request was due). The generator and the scheduler share
one thread and ``add_request`` takes the lock ``step()`` holds, so this is
the wait for the step in progress; TTFT counts it, being taken from the due
time."""
from perfbench.harness import serve_view as view
from perfbench.harness.stats import percentile

UNIT, SOURCE = "ms", "host_clock"


def read(rec):
    if rec["kind"] != "serve" or rec["closed_loop"]:
        return None
    return percentile([(r.sent_s - r.due_s) * 1e3 for r in view.scored(rec)
                       if r.sent_s is not None], 99)
