"""Views of a train runner's records that its metric readers share. Times
are seconds on the run clock, which starts with the window."""

from __future__ import annotations


def tokens_per_s(rec: dict):
    """Tokens of the whole steps inside the window over their time."""
    ends = rec["step_ends_s"]
    if not ends:
        return None
    return (len(ends) * rec["tokens_per_step"]
            / (ends[-1] - rec["window_start_s"]))


def step_times_s(rec: dict) -> list:
    ends = [rec["window_start_s"]] + list(rec["step_ends_s"])
    return [b - a for a, b in zip(ends, ends[1:])]
