"""Views of a serve runner's records that its metric readers share.

A request is *scored* if it was due inside the scored part of the window:
the whole window in an untraced run, the window up to the traced slice in a
traced one. Times are seconds on the run clock.
"""

from __future__ import annotations


def scored_span(rec: dict):
    w0, w1 = rec["window"]
    return w0, min(w1, rec["score_end_s"])


def scored(rec: dict) -> list:
    a, b = scored_span(rec)
    return [r for r in rec["requests"] if a <= r.due_s < b]


def steps_in(rec: dict, a: float, b: float) -> list:
    """Step records ``(t0, t1, prefills, running, used_blocks, queue_depth,
    live_tokens)`` that started inside ``[a, b)``."""
    return [s for s in rec["steps"] if a <= s[0] < b]


def ttft_s(rec: dict) -> list:
    """Seconds from when each scored request was due to its first token's
    host time; for one still without a token when scoring ended, the wait so
    far (a lower bound). Refused requests have none."""
    end = rec["score_end_s"]
    return [(r.first_s if r.first_s is not None else end) - r.due_s
            for r in scored(rec) if r.rejected is None]


def tpot_s(rec: dict) -> list:
    """Mean gap between the tokens each scored request had by the end of
    scoring (two at least)."""
    return [(r.last_s - r.first_s) / (r.tokens - 1)
            for r in scored(rec) if r.tokens >= 2]
