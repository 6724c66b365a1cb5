"""Closed loop: ``clients`` callers, each sends its next request the moment
its last one leaves the system, so exactly ``clients`` are outstanding.

Each client's requests are its own seeded sequence, whatever the order in
which they complete. At time 0 every client sends one; the first
``ramp.residents`` of them (as many as the server has slots) carry the
output length a request found in service would have left, so the run starts
near its steady state. Traffic file: ``clients``, ``classes``, ``ramp``
(``seconds``, ``residents``), ``grace_s`` (0: a throughput cell stops at the
window's end).
"""

from __future__ import annotations

from perfbench.harness.load import LoadRequest, Phases, draw_requests

# requests drawn for a client at a time
_CHUNK = 16


class ClosedLoop:
    def __init__(self, traffic: dict, seed: int, vocab_size: int,
                 seconds: float):
        ramp = traffic["ramp"]
        self.phases = Phases(float(ramp["seconds"]), float(seconds),
                             float(traffic.get("grace_s", 0.0)))
        self.outstanding_target = int(traffic["clients"])
        self._traffic, self._seed, self._vocab = traffic, seed, vocab_size
        self._queues = [[] for _ in range(self.outstanding_target)]
        self._drawn = [0] * self.outstanding_target
        self._count = 0
        first = draw_requests(traffic, self.outstanding_target, seed, (0,),
                              vocab_size,
                              residual_first=int(ramp.get("residents", 0)))
        self._due = [self._new(c, 0.0, p, o)
                     for c, (p, o) in enumerate(first)]
        self.schedule = list(self._due)

    def _new(self, client, due_s, prompt, out_tokens):
        req = LoadRequest(self._count, due_s, prompt, out_tokens,
                          client=client)
        self._count += 1
        return req

    def _next_of(self, client: int):
        if not self._queues[client]:
            self._drawn[client] += 1
            self._queues[client] = draw_requests(
                self._traffic, _CHUNK, self._seed,
                (1, client, self._drawn[client]), self._vocab)
        return self._queues[client].pop(0)

    def pop_due(self, now_s: float) -> list:
        out, self._due = self._due, []
        return out

    def next_due_s(self):
        return self._due[0].due_s if self._due else None

    def finished(self, request: LoadRequest, now_s: float) -> None:
        if now_s >= self.phases.end_s:
            return
        prompt, out_tokens = self._next_of(request.client)
        req = self._new(request.client, now_s, prompt, out_tokens)
        self._due.append(req)
        self.schedule.append(req)


def make(traffic: dict, seed: int, vocab_size: int, seconds: float):
    return ClosedLoop(traffic, seed, vocab_size, seconds)
