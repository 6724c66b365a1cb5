"""What a serving load generator hands the runner, and the phases of a run.

A run has three phases on one clock that starts at 0 when the ramp starts:
``ramp`` (unscored: the system is brought near its steady state), the
``window`` of ``--seconds`` seconds (requests due inside it are scored, and
tokens emitted inside it are counted), and ``grace`` (load goes on, so that
requests due late in the window finish their first tokens under the same
contention; scoring stops at its end and nothing waits for long outputs).

A generator module offers ``make(traffic, seed, vocab_size, seconds)`` and
returns an object with:

    phases                    -> Phases
    pop_due(now_s)            -> [LoadRequest] due by now, in due order
    next_due_s()              -> the next due time, or None
    finished(request, now_s)  -> told of every request that left the system
    outstanding_target        -> requests a closed loop keeps in flight
                                 (None for an open loop)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

# seed streams: one generator never consumes another's numbers
STREAM_ARRIVALS, STREAM_LENGTHS, STREAM_TOKENS, STREAM_WEIGHTS = range(4)


def rng(seed: int, stream: int, *more: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream, *more])


@dataclass
class Phases:
    ramp_s: float
    window_s: float
    grace_s: float

    @property
    def window(self):
        return self.ramp_s, self.ramp_s + self.window_s

    @property
    def end_s(self) -> float:
        return self.ramp_s + self.window_s + self.grace_s


@dataclass
class LoadRequest:
    index: int
    due_s: float                    # when it was due to be sent
    prompt: np.ndarray              # token ids, int32
    out_tokens: int                 # it runs to exactly this many
    client: Optional[int] = None    # closed loop: whose request it is
    # filled in by the runner, same clock
    sent_s: Optional[float] = None      # add_request returned
    rejected: Optional[str] = None      # add_request raised
    first_s: Optional[float] = None     # first token's on_token
    last_s: Optional[float] = None      # latest token's on_token
    tokens: int = 0
    finish_reason: Optional[str] = None
    finished_s: Optional[float] = None
    rid: Optional[int] = None
    # from the scheduler's request tracer, after the run
    admit_s: Optional[float] = None     # start of its first admit phase
    prefill_s: float = 0.0              # its prefill + sampling_sync spans


def draw_class(traffic: dict, n: int, r: np.random.Generator) -> np.ndarray:
    """Index into ``traffic["classes"]`` for each of ``n`` requests, by the
    classes' weights (stratified like the lengths: fixed counts)."""
    w = np.array([c.get("weight", 1.0) for c in traffic["classes"]], float)
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[: n - counts.sum()] += 1
    return r.permutation(np.repeat(np.arange(len(w)), counts))


def draw_requests(traffic: dict, n: int, seed: int, key: tuple,
                  vocab_size: int, residual_first: int = 0):
    """``n`` (prompt, out_tokens) pairs of this mix. ``key`` names the
    sub-stream (a phase, a client), so that what one phase draws does not
    shift another's. The first ``residual_first`` get the output length a
    request found in service would have left."""
    from perfbench.harness import draws

    rl = rng(seed, STREAM_LENGTHS, *key)
    rt = rng(seed, STREAM_TOKENS, *key)
    cls = draw_class(traffic, n, rl)
    prompt_len = np.zeros(n, np.int64)
    out_len = np.zeros(n, np.int64)
    for ci, c in enumerate(traffic["classes"]):
        idx = np.flatnonzero(cls == ci)
        prompt_len[idx] = draws.lengths(c["prompt_tokens"], len(idx), rl)
        out_len[idx] = draws.lengths(c["output_tokens"], len(idx), rl)
        early = idx[idx < residual_first]
        out_len[early] = draws.residual(c["output_tokens"], len(early), rl)
    return [(rt.integers(0, vocab_size, int(p)).astype(np.int32), int(o))
            for p, o in zip(prompt_len, out_len)]
