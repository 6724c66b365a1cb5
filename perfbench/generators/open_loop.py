"""Open loop: requests are sent on a schedule drawn from the seed, whether
or not earlier ones have finished, and each is timed from when it was due.

Traffic file: ``arrivals`` (a process of ``harness/draws.py``),
``classes`` (weight, prompt and output length distributions), ``ramp``
(``seconds`` and ``residents``: requests put in at time 0 with the output
lengths of requests found in service, about rate x service time of them, so
that a short ramp reaches the steady state), ``grace_s``.
"""

from __future__ import annotations

from collections import deque

from perfbench.harness import draws
from perfbench.harness.load import (
    STREAM_ARRIVALS, LoadRequest, Phases, draw_requests, rng)


class OpenLoop:
    outstanding_target = None

    def __init__(self, traffic: dict, seed: int, vocab_size: int,
                 seconds: float):
        ramp = traffic["ramp"]
        self.phases = Phases(float(ramp["seconds"]), float(seconds),
                             float(traffic["grace_s"]))
        residents = int(ramp.get("residents", 0))
        edges = (0.0, self.phases.ramp_s, self.phases.window[1],
                 self.phases.end_s)
        due, reqs = [0.0] * residents, []
        reqs += draw_requests(traffic, residents, seed, (0,), vocab_size,
                              residual_first=residents)
        for phase, (a, b) in enumerate(zip(edges, edges[1:]), start=1):
            t = draws.arrivals(traffic["arrivals"], a, b,
                               rng(seed, STREAM_ARRIVALS, phase))
            due += [float(x) for x in t]
            reqs += draw_requests(traffic, len(t), seed, (phase,),
                                  vocab_size)
        self.schedule = [LoadRequest(i, d, p, o)
                         for i, (d, (p, o)) in enumerate(zip(due, reqs))]
        self._pending = deque(self.schedule)

    def pop_due(self, now_s: float) -> list:
        out = []
        while self._pending and self._pending[0].due_s <= now_s:
            out.append(self._pending.popleft())
        return out

    def next_due_s(self):
        return self._pending[0].due_s if self._pending else None

    def finished(self, request: LoadRequest, now_s: float) -> None:
        pass


def make(traffic: dict, seed: int, vocab_size: int, seconds: float):
    return OpenLoop(traffic, seed, vocab_size, seconds)
