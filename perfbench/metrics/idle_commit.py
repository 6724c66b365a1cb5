"""Share of the traced slice in which the device was idle while the
scheduler committed a step's tokens (``serving.commit``: advance each slot,
emit the token to the caller's ``on_token`` (the benchmark's
``bench.on_token`` runs inside it), retire finished requests)."""
from perfbench.harness import phases

UNIT, SOURCE = "%", "program_span"
SPANS = ("serving.commit",)


def read(rec):
    return phases.share(rec, SPANS)
