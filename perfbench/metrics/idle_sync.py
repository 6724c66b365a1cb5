"""Share of the traced slice in which the device was idle inside the
blocking reads of a step's tokens and telemetry (``serving.sampling_sync``):
the tail of the read after the device's last operation, and the second read
(the telemetry block) after the first (the tokens)."""
from perfbench.harness import phases

UNIT, SOURCE = "%", "program_span"
SPANS = ("serving.sampling_sync",)


def read(rec):
    return phases.share(rec, SPANS)
