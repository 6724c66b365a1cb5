"""Median time between the host reads of consecutive steps' losses (the
loop keeps one step in flight, so in steady state this is the device's step
time)."""
from perfbench.harness import train_view
from perfbench.harness.stats import percentile

UNIT, SOURCE = "ms", "host_clock"


def read(rec):
    if rec["kind"] != "train":
        return None
    p = percentile(train_view.step_times_s(rec), 50)
    return None if p is None else p * 1e3
