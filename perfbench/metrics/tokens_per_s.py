"""Serving above the knee: output tokens stamped in ``on_token`` inside the
window, over the window. Training: tokens of the whole steps inside the
window over their time, the last step ending in a host read of its loss.
Not reported below the knee, where it equals the offered rate."""
from perfbench.harness import serve_view as view
from perfbench.harness import train_view

UNIT, SOURCE = "tokens/s", "host_clock"


def read(rec):
    if rec["kind"] == "train":
        return train_view.tokens_per_s(rec)
    if rec["kind"] == "serve" and rec["closed_loop"]:
        a, b = view.scored_span(rec)
        return sum(a <= t < b for t in rec["tokens_at"]) / (b - a)
    return None
