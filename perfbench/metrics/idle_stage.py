"""Share of the traced slice in which the device was idle while the
scheduler built the device inputs of a launch (``serving.stage``: ids,
positions, the block table and positions of every layer's cache slot, each
a ``to_tensor`` upload). ``harness/phases.py`` says how idle time is given
to spans; the seven ``idle_*`` shares add up to ``device_idle``."""
from perfbench.harness import phases

UNIT, SOURCE = "%", "program_span"
SPANS = ("serving.stage",)


def read(rec):
    return phases.share(rec, SPANS)
