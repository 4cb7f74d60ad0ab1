"""The plain reference: exact optima by methods that share nothing with the
program, and checks of a returned solution against its own graph.

* Minimum vertex cover = n - maximum clique of the complement, by a
  bitset branch and bound with the greedy-colouring bound (Tomita and
  Seki's MCQ, with San Segundo's bitset colouring), on Python integers.
* Minimum dominating set = the 0/1 program min sum x, (A + I) x >= 1,
  solved exactly by SciPy's HiGHS with no optimality gap.

Only numpy and scipy: nothing of the program is imported here.
"""

from __future__ import annotations

import numpy as np

WORD = 32


def max_clique(dense: np.ndarray) -> int:
    """Size of a maximum clique of the graph ``dense`` (bool[n, n])."""
    n = dense.shape[0]
    if n == 0:
        return 0
    deg = dense.sum(axis=1)
    order = sorted(range(n), key=lambda v: (-deg[v], v))
    pos = {v: i for i, v in enumerate(order)}
    nbr = [0] * n
    for v in range(n):
        bits = 0
        for u in np.flatnonzero(dense[v]):
            bits |= 1 << pos[int(u)]
        nbr[pos[v]] = bits
    best = 0

    def expand(size: int, cand: int) -> None:
        nonlocal best
        # Greedy colouring of ``cand`` in bit order; vertices whose colour
        # cannot lift the clique past ``best`` are never branched on.
        verts, colours = [], []
        left, colour, kmin = cand, 0, best - size
        while left:
            colour += 1
            q = left
            while q:
                low = q & -q
                v = low.bit_length() - 1
                q &= ~nbr[v] & ~low
                left &= ~low
                if colour > kmin:
                    verts.append(v)
                    colours.append(colour)
        for i in range(len(verts) - 1, -1, -1):
            if size + colours[i] <= best:
                return
            v = verts[i]
            sub = cand & nbr[v]
            if sub:
                expand(size + 1, sub)
            elif size + 1 > best:
                best = size + 1
            cand &= ~(1 << v)

    expand(0, (1 << n) - 1)
    return best


def min_vertex_cover(dense: np.ndarray) -> int:
    n = dense.shape[0]
    return n - max_clique(~dense & ~np.eye(n, dtype=bool))


def min_dominating_set(dense: np.ndarray) -> int:
    from scipy.optimize import Bounds, LinearConstraint, milp

    n = dense.shape[0]
    closed = (dense | np.eye(n, dtype=bool)).astype(float)
    res = milp(np.ones(n), integrality=np.ones(n), bounds=Bounds(0, 1),
               constraints=LinearConstraint(closed, 1, np.inf),
               options={"mip_rel_gap": 0.0})
    if not res.success:
        raise RuntimeError(f"reference dominating set failed: {res.message}")
    return int(round(res.fun))


OPTIMUM = {"vc": min_vertex_cover, "ds": min_dominating_set}


def unpack(words: np.ndarray, n: int) -> np.ndarray:
    """bool[n] from a packed uint32 bitset (bit v of word v // 32)."""
    words = np.asarray(words, dtype=np.uint32).reshape(-1)
    v = np.arange(n)
    return ((words[v // WORD] >> (v % WORD).astype(np.uint32)) & 1) == 1


def is_solution(family: str, dense: np.ndarray, chosen: np.ndarray) -> bool:
    """Whether the vertex set ``chosen`` (bool[n]) is a vertex cover (vc)
    or a dominating set (ds) of ``dense``."""
    if family == "vc":
        i, j = np.nonzero(np.triu(dense, k=1))
        return bool(np.all(chosen[i] | chosen[j]))
    if family == "ds":
        closed = dense | np.eye(dense.shape[0], dtype=bool)
        return bool(np.all((closed & chosen[None, :]).any(axis=1)))
    raise ValueError(f"unknown family {family!r}")


def solution_gap(family: str, dense: np.ndarray, words: np.ndarray,
                 value: int) -> bool:
    """True where the packed ``words`` are not a solution of ``dense`` of
    exactly ``value`` vertices, or carry a vertex beyond ``n``."""
    n = dense.shape[0]
    words = np.asarray(words, dtype=np.uint32).reshape(-1)
    total = int(np.bitwise_count(words).sum())
    chosen = unpack(words, n)
    return not (int(chosen.sum()) == total == value
                and is_solution(family, dense, chosen))
