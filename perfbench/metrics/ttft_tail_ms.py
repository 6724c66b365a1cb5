"""90th percentile of the same times as ``ttft_p50_ms``: the highest
percentile that keeps ten samples beyond it at ~100 requests a window. Not an
end-to-end metric: over 120 requests it spread by 10 to 68 % between runs of
the same code (chip runs, PR 22), far outside any bound the contract allows."""
from perfbench.harness import serve_view as view
from perfbench.harness.stats import percentile

UNIT, SOURCE = "ms", "host_clock"


def read(rec):
    if rec["kind"] != "serve" or rec["closed_loop"]:
        return None
    p = percentile(view.ttft_s(rec), 90)
    return None if p is None else p * 1e3
