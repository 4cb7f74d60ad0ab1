"""Panel traffic: the same few hard instances, solved whole, pass after pass.

Mix parameters:
  instance_seeds  G(n, p) seeds of the panel; n and p are the
                  configuration's ``graph``.

Every ``--seed`` solves the same instances, so every run does the same
work; the seed only orders each pass.  (Fresh draws of G(125, 0.10) differ
by 2x and more in nodes, which would make the seed, not the program, the
largest source of spread.)
"""

from __future__ import annotations

import numpy as np

from gnp import gnp_dense


class Panel:
    def __init__(self, config: dict, mix: dict, seed: int):
        g = config["graph"]
        self.seeds = [int(s) for s in mix["instance_seeds"]]
        self.names = [f"gnp_{g['n']}_{g['p']}_{s}" for s in self.seeds]
        self.dense = [gnp_dense(g["n"], g["p"], s) for s in self.seeds]
        self.seed = int(seed) & (2**64 - 1)

    def order(self, pass_no: int) -> np.ndarray:
        """The instance order of pass ``pass_no`` (a seeded permutation)."""
        rng = np.random.default_rng([self.seed, pass_no])
        return rng.permutation(len(self.dense))


def make(config: dict, mix: dict, seed: int) -> Panel:
    return Panel(config, mix, seed)
