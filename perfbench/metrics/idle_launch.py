"""Share of the traced slice in which the device was idle while the
scheduler called the compiled step on ready arguments (``serving.launch``:
argument handling, the sampling key's split, the enqueue, and taking the
returned pools) or was elsewhere inside ``serving.decode_step`` /
``serving.prefill`` than in their staging and launch."""
from perfbench.harness import phases

UNIT, SOURCE = "%", "program_span"
SPANS = ("serving.launch", "serving.decode_step", "serving.prefill")


def read(rec):
    return phases.share(rec, SPANS)
