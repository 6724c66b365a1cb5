"""Median, over the requests due in the scored window, of the time from
when a request was due to be sent to its first token's host time. Not an
end-to-end metric: a request waits for the decode step in progress, a wait
uniform over 0-106 ms, and the median of ~120 such times spreads by 4 to 7 %
between runs of the same code (chip runs, PR 22), more than half the widest
bound the contract allows; it would take ~500 requests a window."""
from perfbench.harness import serve_view as view
from perfbench.harness.stats import percentile

UNIT, SOURCE = "ms", "host_clock"


def read(rec):
    if rec["kind"] != "serve" or rec["closed_loop"]:
        return None
    p = percentile(view.ttft_s(rec), 50)
    return None if p is None else p * 1e3
