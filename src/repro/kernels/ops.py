"""jit'd dispatch wrappers for the Pallas kernels.

``use_pallas=None`` runs the Pallas kernel on every platform but the CPU,
where the pure-jnp oracle runs instead (CPU smoke/benchmarks).  A kernel
that does run interprets only on the CPU platform or when a caller passes
``interpret=True`` (``autotune.resolve_interpret``); on a device it
compiles or the call raises.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import bitset_ops, ref
from repro.kernels.bitset_degree import degree_argmax as _degree_pallas
from repro.kernels.bitset_degree import degree_stats as _degree_stats_pallas


def _use_pallas(use_pallas: Optional[bool]) -> bool:
    if use_pallas is None:
        return jax.default_backend() != "cpu"
    return use_pallas


@partial(jax.jit, static_argnames=("tile", "stages", "use_pallas",
                                   "interpret"))
def degree_stats(adj, alive, *, tile: Optional[int] = None,
                 stages: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """(best_degree, best_vertex, degree_sum) per lane — the fused
    vertex-cover node statistics (see problems.vertex_cover)."""
    if _use_pallas(use_pallas):
        return _degree_stats_pallas(adj, alive, tile=tile, stages=stages,
                                    interpret=interpret)
    return ref.degree_stats_ref(adj, alive)


@partial(jax.jit, static_argnames=("tile", "stages", "use_pallas",
                                   "interpret"))
def degree_argmax(adj, alive, *, tile: Optional[int] = None,
                  stages: Optional[int] = None,
                  use_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    if _use_pallas(use_pallas):
        return _degree_pallas(adj, alive, tile=tile, stages=stages,
                              interpret=interpret)
    return ref.degree_argmax_ref(adj, alive)


def _dispatch(pallas_fn, ref_fn, args, *, use_pallas, interpret,
              kernel_kw=None, ref_kw=None):
    """Shared backend resolution for the bitset_ops dispatchers: Pallas off
    the CPU (or when forced), jnp oracle on the CPU; ``interpret=None`` is
    resolved by the kernel itself (interpret on the CPU only)."""
    if _use_pallas(use_pallas):
        return pallas_fn(*args, interpret=interpret, **(kernel_kw or {}))
    return ref_fn(*args, **(ref_kw or {}))


@partial(jax.jit, static_argnames=("tile", "stages", "use_pallas",
                                   "interpret"))
def count_stats(table, mask, valid, *, tile: Optional[int] = None,
                stages: Optional[int] = None,
                use_pallas: Optional[bool] = None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """The universal masked-popcount pass (DESIGN.md §5.2):
    (best_count, best_vertex, count_sum, mask_count) per lane.
    ``tile``/``stages`` default to the autotuner (DESIGN.md §5.6)."""
    return _dispatch(bitset_ops.count_stats, ref.count_stats_ref,
                     (table, mask, valid), use_pallas=use_pallas,
                     interpret=interpret,
                     kernel_kw={"tile": tile, "stages": stages})


@partial(jax.jit, static_argnames=("tile", "stages", "use_pallas",
                                   "interpret"))
def stacked_count_stats(tables, inst, mask, valid, *,
                        tile: Optional[int] = None,
                        stages: Optional[int] = None,
                        use_pallas: Optional[bool] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Batched uint32[K, n, w] masked-popcount pass (DESIGN.md §5.3) —
    each lane reduced against its instance's table; idle (inst < 0)
    lanes park on the (-1, -1, 0, 0) row."""
    return _dispatch(bitset_ops.stacked_count_stats,
                     ref.stacked_count_stats_ref,
                     (tables, inst, mask, valid), use_pallas=use_pallas,
                     interpret=interpret,
                     kernel_kw={"tile": tile, "stages": stages})


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def popcount_reduce(rows, *, use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """uint32[L, w] -> int32[L] packed-set cardinalities."""
    return _dispatch(bitset_ops.popcount_reduce, ref.popcount_reduce_ref,
                     (rows,), use_pallas=use_pallas, interpret=interpret)


@partial(jax.jit, static_argnames=("op", "tile", "use_pallas", "interpret"))
def masked_row_reduce(table, select, *, op: str = "or", tile: int = 128,
                      use_pallas: Optional[bool] = None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """OR/AND-accumulate of table rows selected by a bitset."""
    return _dispatch(bitset_ops.masked_row_reduce, ref.masked_row_reduce_ref,
                     (table, select), use_pallas=use_pallas,
                     interpret=interpret,
                     kernel_kw={"op": op, "tile": tile}, ref_kw={"op": op})


@partial(jax.jit, static_argnames=("tile", "stages", "use_pallas",
                                   "interpret"))
def domination_stats(cadj, dominated, cand, fullm, *,
                     tile: Optional[int] = None,
                     stages: Optional[int] = None,
                     use_pallas: Optional[bool] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """(best_coverage, branch_vertex, undominated) per lane — the fused
    dominating-set node statistics (see problems.dominating_set)."""
    return _dispatch(bitset_ops.domination_stats, ref.domination_stats_ref,
                     (cadj, dominated, cand, fullm), use_pallas=use_pallas,
                     interpret=interpret,
                     kernel_kw={"tile": tile, "stages": stages})
