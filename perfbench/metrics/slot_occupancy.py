"""Occupied share of the slot grid: ``metrics.running / max_num_seqs`` read
after every ``step()``, weighted by the step's duration."""
from perfbench.harness import serve_view as view

UNIT, SOURCE = "%", "program_counter"


def read(rec):
    if rec["kind"] != "serve":
        return None
    steps = view.steps_in(rec, *view.scored_span(rec))
    total = sum(s[1] - s[0] for s in steps)
    if not total:
        return None
    return (100.0 * sum((s[1] - s[0]) * s[3] for s in steps)
            / (total * rec["max_num_seqs"]))
