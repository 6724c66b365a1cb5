"""Share of the traced slice in which the device was idle and the
scheduler's thread was in no ``serving.step``: the benchmark's own loop
between two calls (injecting due requests, the step record) and, when
nothing is in service, its wait for the next request to fall due
(``bench.wait_due``: no load offered, as ``slot_occupancy`` shows). Not the
program's to shorten."""
from perfbench.harness import phases

UNIT, SOURCE = "%", "program_span"
SPANS = (phases.OUTSIDE,)


def read(rec):
    return phases.share(rec, SPANS)
