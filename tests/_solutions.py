"""Feasibility checks for the solutions the solver returns."""

import numpy as np


def members(bits, n: int) -> np.ndarray:
    """bool[n]: the vertices whose bit is set in a packed uint32[w] set.
    No bit at or past ``n`` may be set."""
    bits = np.asarray(bits, np.uint32).reshape(-1)
    v = np.arange(32 * bits.size)
    on = ((bits[v // 32] >> (v % 32).astype(np.uint32)) & 1).astype(bool)
    assert not on[n:].any(), "a bit past the last vertex is set"
    return on[:n]


def _dense(graph) -> np.ndarray:
    return np.stack([members(row, graph.n) for row in graph.adj])


def assert_feasible(family: str, instance, payload, value: int) -> None:
    """``payload`` is a solution of ``instance`` whose value is ``value``."""
    if family == "vc":
        cover = members(payload, instance.n)
        adj = _dense(instance)
        assert not (adj & ~cover[:, None] & ~cover[None, :]).any(), \
            "an edge has neither end in the cover"
        assert int(cover.sum()) == value
    elif family == "ds":
        chosen = members(payload, instance.n)
        adj = _dense(instance)
        assert (chosen | (adj & chosen[None, :]).any(axis=1)).all(), \
            "a vertex is neither chosen nor adjacent to a chosen one"
        assert int(chosen.sum()) == value
    elif family == "ss":
        taken = np.asarray(payload).astype(bool)
        assert int(np.asarray(instance.values)[taken].sum()) == \
            instance.target
        assert int(taken.sum()) == value
    else:
        raise AssertionError(f"no feasibility check for family {family!r}")
