"""Share of the traced slice in which the device was idle in admission's
own code (``serving.admit`` less its children: queue pops, request set-up,
slot packing, the request tracer) or in the prefix cache's match
(``serving.prefix_match``). An admission's allocation, staging, launch,
read and first-token commit are their own phases."""
from perfbench.harness import phases

UNIT, SOURCE = "%", "program_span"
SPANS = ("serving.admit", "serving.prefix_match")


def read(rec):
    return phases.share(rec, SPANS)
