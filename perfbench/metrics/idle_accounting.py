"""Share of the traced slice in which the device was idle in the
scheduler's bookkeeping: block allocation, extension and preemption
(``serving.block_accounting``, ``serving.preempt``), the deadline sweep and
the shed ladder (``serving.sweep``), the step's gauges, flight record and
alarms (``serving.account``), draft proposals (``serving.spec_propose``),
and what of ``serving.step`` no named phase covers."""
from perfbench.harness import phases

UNIT, SOURCE = "%", "program_span"
SPANS = ("serving.block_accounting", "serving.preempt", "serving.sweep",
         "serving.account", "serving.spec_propose", phases.STEP)


def read(rec):
    return phases.share(rec, SPANS)
