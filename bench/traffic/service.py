"""Request traffic for the solver service.

Mix parameters:
  pool_seed, pool_size  a fixed pool of instances: each request family of
                        the configuration (``requests``) gets its ``share``
                        of the pool, with sizes spread evenly over its
                        ``n`` range, drawn as G(n, p) from ``pool_seed``.
  arrivals              {"process": "poisson", "rate": r}: open loop,
                        ``floor(r * seconds)`` requests due in the window;
                        {"process": "backlog", "depth": d}: the queue is
                        topped up to ``d`` waiting requests after every
                        service round.
  drain_s               how long after the window requests still open are
                        followed.

The seed orders the pool (a fresh permutation per pass over it) and
relabels every submission's vertices (an isomorphic copy: the optimum and
the pool's work stay, the bytes differ, so no two submissions repeat).
Poisson gaps are the ``N`` stratified quantiles of the exponential in a
seeded order: every seed offers the same load, in another order.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from gnp import gnp_dense, relabel


class PoolEntry(NamedTuple):
    family: str
    n: int
    dense: np.ndarray


class Request(NamedTuple):
    index: int          # position in the stream (the request id)
    pool: int           # pool entry it is a relabelling of
    family: str
    dense: np.ndarray


def make_pool(requests: list, pool_seed: int, pool_size: int) -> list:
    pool = []
    for f, spec in enumerate(requests):
        count = int(round(pool_size * spec["share"]))
        lo, hi = spec["n"]
        sizes = np.rint(np.linspace(lo, hi, count)).astype(int)
        for i, n in enumerate(sizes):
            seed = pool_seed * 100_000 + f * 10_000 + i
            pool.append(PoolEntry(spec["family"], int(n),
                                  gnp_dense(int(n), spec["p"], seed)))
    return pool


class Stream:
    def __init__(self, config: dict, mix: dict, seed: int):
        self.pool = make_pool(config["requests"], int(mix["pool_seed"]),
                              int(mix["pool_size"]))
        self.arrivals = dict(mix["arrivals"])
        self.seed = int(seed) & (2**64 - 1)
        self._orders = {}

    def _order(self, pass_no: int) -> np.ndarray:
        if pass_no not in self._orders:
            rng = np.random.default_rng([self.seed, 0, pass_no])
            self._orders[pass_no] = rng.permutation(len(self.pool))
        return self._orders[pass_no]

    def request(self, j: int) -> Request:
        pass_no, pos = divmod(j, len(self.pool))
        k = int(self._order(pass_no)[pos])
        entry = self.pool[k]
        perm = np.random.default_rng([self.seed, 1, j]).permutation(entry.n)
        return Request(j, k, entry.family, relabel(entry.dense, perm))

    def due_times(self, seconds: float) -> Optional[np.ndarray]:
        """Seconds after the window opens at which each request is due
        (open loop), or None for a standing backlog."""
        if self.arrivals["process"] == "backlog":
            return None
        rate = float(self.arrivals["rate"])
        count = int(rate * seconds)
        u = (np.arange(count) + 0.5) / count
        gaps = -np.log1p(-u) / rate
        gaps = np.random.default_rng([self.seed, 2]).permutation(gaps)
        return np.cumsum(gaps)

    @property
    def depth(self) -> int:
        return int(self.arrivals["depth"])


def make(config: dict, mix: dict, seed: int) -> Stream:
    return Stream(config, mix, seed)
