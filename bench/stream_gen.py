"""Seeded drift token stream: the one traffic generator of the benchmark.

Every item is ``batch`` rows of ``seq`` next-token pairs drawn from a
Markov source over the configuration's vocabulary. The source drifts
between two sparse transition kernels ``T0`` and ``T1``: at round ``m`` a
token steps through ``T1`` with probability ``mix(m)`` and through ``T0``
otherwise, which samples the mixture ``(1 - mix) T0 + mix T1`` without
building it. ``mix`` is a raised cosine with the traffic's period, so the
distribution keeps shifting for as long as the stream runs.

Both kernels are built once, from the seed, with ``successors`` next
states per state. Round ``m`` draws its randomness from its own generator
seeded by ``(seed, m)``, so the rows of a round do not depend on how the
stream is cut into takes, and every row is its own chain started from a
seeded state: a take of ``n`` rounds steps ``n * batch`` chains at once.
"""

from __future__ import annotations

import math
import time
from typing import Dict

import numpy as np

Rows = Dict[str, np.ndarray]


class DriftStream:
    def __init__(
        self, vocab: int, batch: int, seq: int, seed: int, *,
        successors: int, period_rounds: int, sharpness: float,
    ):
        self.vocab, self.batch, self.seq = int(vocab), int(batch), int(seq)
        self.seed = int(seed)
        self.period = int(period_rounds)
        rng = np.random.default_rng([self.seed, 0])
        k = int(successors)
        self.next = rng.integers(0, self.vocab, size=(2, self.vocab, k), dtype=np.int32)
        w = rng.random((2, self.vocab, k)) ** float(sharpness)
        cum = np.cumsum(w, axis=-1)
        cum /= cum[..., -1:]
        cum[..., -1] = 1.0  # u < 1 always picks a successor
        self.cum = cum
        self.gen_s = 0.0  # host seconds spent generating
        self.rounds_made = 0

    def mix(self, m: int) -> float:
        return 0.5 - 0.5 * math.cos(2.0 * math.pi * m / self.period)

    def rows(self, start: int, n: int) -> Rows:
        """Rounds ``[start, start + n)`` as ``tokens``/``labels`` of shape
        ``(n, batch, seq)``, int32."""
        t0 = time.perf_counter()
        b, s = self.batch, self.seq
        u = np.empty((n * b, s), np.float64)
        pick = np.empty((n * b, s), np.int32)
        state = np.empty(n * b, np.int32)
        for i in range(n):
            m = start + i
            r = np.random.default_rng([self.seed, 1, m])
            rows = slice(i * b, (i + 1) * b)
            state[rows] = r.integers(0, self.vocab, size=b)
            u[rows] = r.random((b, s))
            pick[rows] = r.random((b, s)) < self.mix(m)
        out = np.empty((n * b, s + 1), np.int32)
        out[:, 0] = state
        for t in range(s):
            kern, cur = pick[:, t], out[:, t]
            j = (self.cum[kern, cur] < u[:, t, None]).sum(axis=1)
            out[:, t + 1] = self.next[kern, cur, j]
        out = out.reshape(n, b, s + 1)
        self.gen_s += time.perf_counter() - t0
        self.rounds_made += n
        return {"tokens": out[:, :, :-1].copy(), "labels": out[:, :, 1:].copy()}

    @classmethod
    def from_traffic(cls, traffic: dict, vocab: int, seed: int) -> "DriftStream":
        return cls(
            vocab, traffic["batch"], traffic["seq"], seed,
            successors=traffic["successors"],
            period_rounds=traffic["drift_period_rounds"],
            sharpness=traffic["sharpness"],
        )
