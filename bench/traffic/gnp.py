"""Erdős–Rényi G(n, p) instances: the data every cell solves.

The draw is the benchmark's own copy of the classic generator (numpy's
``RandomState(seed)``: the upper triangle of ``rand(n, n) < p``), so a
change to the program's generators cannot change what is measured.  The
program receives the boolean adjacency only through its public input type,
``repro.problems.graphs.pack_adjacency``.
"""

from __future__ import annotations

import numpy as np


def gnp_dense(n: int, p: float, seed: int) -> np.ndarray:
    """bool[n, n] symmetric adjacency of G(n, p), no self loops."""
    rng = np.random.RandomState(seed)
    upper = np.triu(rng.rand(n, n) < p, k=1)
    return upper | upper.T


def relabel(dense: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """The isomorphic copy in which vertex ``i`` is the old ``perm[i]``:
    every optimum is unchanged, the bytes the program sees are not."""
    return dense[np.ix_(perm, perm)]


def to_graph(dense: np.ndarray, name: str):
    """The program's input type for ``dense`` (imported on use, so this
    module loads without the program)."""
    from repro.problems.graphs import pack_adjacency
    return pack_adjacency(dense, name)
