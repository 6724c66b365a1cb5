"""Sequences evicted for want of KV blocks: ``ServingMetrics.preemptions``,
its change from the window's start to the end of the run."""
UNIT, SOURCE = "count", "program_counter"


def read(rec):
    return rec["preemptions"] if rec["kind"] == "serve" else None
