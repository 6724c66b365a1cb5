"""By how much the profiler stretches the slice every ``device_trace``
metric is read from: the median ``serving.step`` span of the traced slice
that launched a decode and no prefill, over ``decode_step_p50_ms`` of the
same run (host clock, the window before the slice, profiler closed), less
one. It holds JAX's own host tracing and the program's spans together."""
from perfbench.harness import phases
from perfbench.harness.spec import load_module

UNIT, SOURCE = "%", "program_span"


def read(rec):
    traced = phases.decode_only_step_ms(rec)
    if traced is None:
        return None
    untraced = load_module("metrics", "decode_step_p50_ms").read(rec)
    return None if not untraced else 100.0 * (traced / untraced - 1.0)
