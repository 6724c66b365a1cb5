"""A pretraining job's input: a new seeded host batch of uniform random
token ids every step, ``ids`` and the ``labels`` one position on.

Traffic file: ``batch``, ``sequence`` (the step's shape: what the job
sends), ``warm_steps`` (unscored, after the compile).
"""

from __future__ import annotations

from perfbench.harness.load import STREAM_TOKENS, rng


class TrainJob:
    def __init__(self, traffic: dict, seed: int, vocab_size: int,
                 seconds: float):
        self.batch = int(traffic["batch"])
        self.sequence = int(traffic["sequence"])
        self.warm_steps = int(traffic["warm_steps"])
        self.seconds = float(seconds)
        self._seed, self._vocab = seed, vocab_size

    def batch_at(self, step: int):
        """Host ``(ids, labels)`` of step ``step``, both ``[batch,
        sequence]`` int32."""
        tok = rng(self._seed, STREAM_TOKENS, step).integers(
            0, self._vocab, (self.batch, self.sequence + 1)).astype("int32")
        return tok[:, :-1].copy(), tok[:, 1:].copy()

    def batches(self, start: int = 0):
        step = start
        while True:
            yield self.batch_at(step)
            step += 1


def make(traffic: dict, seed: int, vocab_size: int, seconds: float):
    return TrainJob(traffic, seed, vocab_size, seconds)
