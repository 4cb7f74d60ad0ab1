"""Vertex-cover degree statistics as a ``bitset_ops`` binding (DESIGN.md §5.4).

The solver's hot spot (paper §V): at every search-node, compute the degree
of every alive vertex in the residual graph — popcount(adj[v] & alive) —
then (a) pick the max-degree vertex with smallest-id tie-break (the branch
rule) and (b) sum the alive degrees (= 2·m_alive, the bound's numerator).
That is exactly the universal masked-popcount pass of
``repro.kernels.bitset_ops.count_stats`` with mask = valid = the alive
set, so this module is a thin argument adapter — the kernel body, grid and
block shapes live in ``bitset_ops`` and are documented in DESIGN.md
§5.1/§5.5; the per-column contract is §5.2.  ``tile``/``stages`` default
to the per-shape autotuner (DESIGN.md §5.6) and ``interpret=None``
interprets on the CPU only and compiles everywhere else.

Kept as a module (rather than folding the call sites into
``problems/vertex_cover.py``) so the kernel library's problem bindings
stay enumerable in one place per problem family, mirroring
``bitset_ops.domination_stats`` for dominating set and
``bitset_ops.stacked_count_stats`` for the stacked service.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import bitset_ops


def degree_stats(adj: jnp.ndarray, alive: jnp.ndarray, *,
                 tile: Optional[int] = None, stages: Optional[int] = None,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """adj: uint32[n, w] packed adjacency; alive: uint32[L, w] per-lane
    masks.  Returns int32[L, 3] = (best_degree, best_vertex, degree_sum);
    (-1, -1, 0) when no vertex is alive.  ``degree_sum`` is the sum of
    alive-vertex degrees, i.e. twice the residual edge count."""
    return bitset_ops.count_stats(adj, alive, alive, tile=tile,
                                  stages=stages, interpret=interpret)[:, :3]


def degree_argmax(adj: jnp.ndarray, alive: jnp.ndarray, *,
                  tile: Optional[int] = None, stages: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Compatibility wrapper: int32[L, 2] = (best_degree, best_vertex)."""
    return degree_stats(adj, alive, tile=tile, stages=stages,
                        interpret=interpret)[:, :2]
