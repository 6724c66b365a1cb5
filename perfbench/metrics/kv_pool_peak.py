"""Most of the KV pool in use at once: ``allocator.num_used_blocks`` over
the pool's blocks, read after every ``step()``, maximum over the window."""
from perfbench.harness import serve_view as view

UNIT, SOURCE = "%", "program_counter"


def read(rec):
    if rec["kind"] != "serve":
        return None
    steps = view.steps_in(rec, *view.scored_span(rec))
    if not steps:
        return None
    return 100.0 * max(s[4] for s in steps) / rec["total_blocks"]
