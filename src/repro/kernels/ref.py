"""Pure-jnp oracles for every Pallas kernel (the allclose references).

bitset_ops      -> count_stats / stacked_count_stats / popcount_reduce /
                   masked_row_reduce / domination_stats below
                   (DESIGN.md §5.2's contract, stated in plain jnp)
bitset_degree   -> degree_stats / degree_argmax below (mirrors
                   problems.vertex_cover)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _bit_set(mask: jnp.ndarray, n: int) -> jnp.ndarray:
    """bool[n]: is bit v of the packed uint32[w] mask set?"""
    vid = jnp.arange(n)
    word = vid // 32
    bit = (vid % 32).astype(jnp.uint32)
    return ((mask[word] >> bit) & jnp.uint32(1)) == jnp.uint32(1)


def _count_stats_one(table: jnp.ndarray, mask: jnp.ndarray,
                     valid: jnp.ndarray) -> jnp.ndarray:
    n = table.shape[0]
    rows = jnp.bitwise_and(table, mask[None, :])
    cnts = jax.lax.population_count(rows).sum(axis=1).astype(jnp.int32)
    cnts = jnp.where(_bit_set(valid, n), cnts, jnp.int32(-1))
    best = jnp.max(cnts)
    arg = jnp.argmax(cnts).astype(jnp.int32)     # first max = smallest id
    total = jnp.sum(jnp.maximum(cnts, 0))
    mcount = jax.lax.population_count(mask).sum().astype(jnp.int32)
    return jnp.stack([best, jnp.where(best < 0, jnp.int32(-1), arg),
                      total, mcount])


def count_stats_ref(table: jnp.ndarray, mask: jnp.ndarray,
                    valid: jnp.ndarray) -> jnp.ndarray:
    """table uint32[n, w]; mask/valid uint32[L, w] -> int32[L, 4] per the
    masked-popcount contract (DESIGN.md §5.2)."""
    return jax.vmap(lambda m, v: _count_stats_one(table, m, v))(mask, valid)


def stacked_count_stats_ref(tables: jnp.ndarray, inst: jnp.ndarray,
                            mask: jnp.ndarray,
                            valid: jnp.ndarray) -> jnp.ndarray:
    """tables uint32[K, n, w]; inst int32[L]; mask/valid uint32[L, w] ->
    int32[L, 4], lane l reduced against tables[inst[l]].  Idle lanes
    (inst < 0, the service's NO_INSTANCE) are parked: their masks are
    zeroed so they return the no-valid row (-1, -1, 0, 0) instead of
    being clipped onto instance 0's table."""
    k = tables.shape[0]
    inst = inst.astype(jnp.int32)
    idle = inst < 0
    mask = jnp.where(idle[:, None], jnp.uint32(0), mask)
    valid = jnp.where(idle[:, None], jnp.uint32(0), valid)
    inst = jnp.clip(inst, 0, k - 1)
    return jax.vmap(
        lambda i, m, v: _count_stats_one(tables[i], m, v))(inst, mask, valid)


def popcount_reduce_ref(rows: jnp.ndarray) -> jnp.ndarray:
    """uint32[L, w] -> int32[L]."""
    return jax.lax.population_count(rows).sum(axis=-1).astype(jnp.int32)


def masked_row_reduce_ref(table: jnp.ndarray, select: jnp.ndarray, *,
                          op: str = "or") -> jnp.ndarray:
    """table uint32[n, w]; select uint32[L, w] -> uint32[L, w]: OR/AND of
    the selected rows (identity for an empty selection)."""
    if op not in ("or", "and"):
        raise ValueError(f"unknown reduce op {op!r}")
    n = table.shape[0]
    ident = jnp.uint32(0) if op == "or" else jnp.uint32(0xFFFFFFFF)
    bitop = jnp.bitwise_or if op == "or" else jnp.bitwise_and

    def one(sel):
        rows = jnp.where(_bit_set(sel, n)[:, None], table, ident)
        return jax.lax.reduce(rows, ident, bitop, (0,))

    return jax.vmap(one)(select)


def domination_stats_ref(cadj: jnp.ndarray, dominated: jnp.ndarray,
                         cand: jnp.ndarray, fullm: jnp.ndarray) -> jnp.ndarray:
    """Dominating set's (best_coverage, branch_vertex, undominated) in plain
    jnp — the oracle for ``bitset_ops.domination_stats``."""
    mask = jnp.bitwise_and(fullm[None, :], jnp.bitwise_not(dominated))
    out = count_stats_ref(cadj, mask, cand)
    return jnp.stack([out[:, 0], out[:, 1], out[:, 3]], axis=1)


def degree_stats_ref(adj: jnp.ndarray, alive: jnp.ndarray) -> jnp.ndarray:
    """adj uint32[n, w]; alive uint32[L, w] -> int32[L, 3] of
    (best_degree, best_vertex, degree_sum); (-1, -1, 0) when nothing is
    alive.  ``degree_sum`` = twice the residual edge count."""
    n, w = adj.shape

    def one(mask):
        rows = jnp.bitwise_and(adj, mask[None, :])
        degs = jax.lax.population_count(rows).sum(axis=1).astype(jnp.int32)
        vid = jnp.arange(n)
        word = vid // 32
        bit = (vid % 32).astype(jnp.uint32)
        is_alive = ((mask[word] >> bit) & jnp.uint32(1)) == jnp.uint32(1)
        degs = jnp.where(is_alive, degs, jnp.int32(-1))
        best = jnp.max(degs)
        arg = jnp.argmax(degs).astype(jnp.int32)   # first max = smallest id
        total = jnp.sum(jnp.maximum(degs, 0))
        return jnp.stack([best, jnp.where(best < 0, jnp.int32(-1), arg),
                          total])

    return jax.vmap(one)(alive)


def degree_argmax_ref(adj: jnp.ndarray, alive: jnp.ndarray) -> jnp.ndarray:
    """adj uint32[n, w]; alive uint32[L, w] -> int32[L, 2]."""
    return degree_stats_ref(adj, alive)[:, :2]
