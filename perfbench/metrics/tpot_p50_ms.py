"""Median over the scored requests of (latest token time - first token
time) / (tokens - 1), from the tokens each had when scoring ended."""
from perfbench.harness import serve_view as view
from perfbench.harness.stats import percentile

UNIT, SOURCE = "ms", "host_clock"


def read(rec):
    if rec["kind"] != "serve" or rec["closed_loop"]:
        return None
    p = percentile(view.tpot_s(rec), 50)
    return None if p is None else p * 1e3
