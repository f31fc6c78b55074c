"""Seeded stream generation: the paper's ZF key stream and its payload.

Copied from ``repro.data.synthetic.zipf_time_evolving`` (paper §6.1, ZF)
so that later changes to the program cannot move the yardstick.  One
change: the §6.1 stream (a hot-head flip at 0.8 of it) is one period of
``period`` tuples, and the stream repeats such periods, so that every
run of any length sees flips at the same stream positions.  Periods are
drawn one after another as a run consumes them.
"""

from __future__ import annotations

import numpy as np

__all__ = ["zipf_probs", "ZFStream"]


def zipf_probs(num_keys: int, z: float) -> np.ndarray:
    ranks = np.arange(1, num_keys + 1, dtype=np.float64)
    p = ranks ** (-z)
    return p / p.sum()


def _flipped_probs(num_keys: int, z: float, flip_head: int) -> np.ndarray:
    # Pr[i] ∝ (k - i + 1)^-z for i in [1, k]; keys beyond k keep tail mass
    ranks = np.arange(1, num_keys + 1, dtype=np.float64)
    k = min(flip_head, num_keys)
    head = np.maximum(k - ranks + 1.0, 1.0) ** (-z)
    tail = np.maximum(ranks - k + 1.0, 1.0) ** (-z)
    p2 = np.where(ranks <= k, head, tail)
    return p2 / p2.sum()


class ZFStream:
    """The tuples of one seed, in order: int32 key ids in [0, num_keys),
    int64 payloads in [payload_min, payload_max], and model-clock arrival
    times ``i / model_rate``.  Each period draws its first
    ``flip_at * period`` keys with ``Pr[i] ∝ i^-z`` and the rest with the
    hot head flipped.  ``take(n)`` hands out the next ``n`` tuples and
    draws periods as it needs them; ``fed()`` returns every tuple handed
    out so far."""

    def __init__(self, config, seed: int):
        c = config
        self.num_keys = c["num_keys"]
        self.period = c["flip_period"]
        self.n1 = int(c["flip_at"] * self.period)
        self.p1 = zipf_probs(self.num_keys, c["zipf_z"])
        self.p2 = _flipped_probs(self.num_keys, c["zipf_z"], c["flip_head"])
        self.low, self.high = c["payload_min"], c["payload_max"]
        self.rate = float(c["model_rate"])
        self._rk = np.random.default_rng(seed)
        self._rv = np.random.default_rng([seed, 1])
        self._keys, self._vals = [], []
        self._drawn = 0
        self.pos = 0

    def _draw(self) -> None:
        k1 = self._rk.choice(self.num_keys, size=self.n1, p=self.p1)
        k2 = self._rk.choice(self.num_keys, size=self.period - self.n1,
                             p=self.p2)
        self._keys.append(np.concatenate([k1, k2]).astype(np.int32))
        self._vals.append(self._rv.integers(self.low, self.high + 1,
                                            self.period).astype(np.int64))
        self._drawn += self.period

    def take(self, n: int):
        """(keys, values, t) of the next ``n`` tuples."""
        while self._drawn < self.pos + n:
            self._draw()
        if len(self._keys) > 1:
            self._keys = [np.concatenate(self._keys)]
            self._vals = [np.concatenate(self._vals)]
        lo, hi = self.pos, self.pos + n
        self.pos = hi
        t = np.arange(lo, hi, dtype=np.float64) / self.rate
        return self._keys[0][lo:hi], self._vals[0][lo:hi], t

    def fed(self):
        """{"keys", "values", "t"} of every tuple handed out so far."""
        keys = (np.concatenate(self._keys)[:self.pos] if self._keys
                else np.zeros(0, np.int32))
        vals = (np.concatenate(self._vals)[:self.pos] if self._vals
                else np.zeros(0, np.int64))
        return {"keys": keys, "values": vals,
                "t": np.arange(self.pos, dtype=np.float64) / self.rate}
