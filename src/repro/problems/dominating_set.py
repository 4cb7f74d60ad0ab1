"""DOMINATING SET via reduction to MINIMUM SET COVER (paper §V, ref [4]).

Universe = vertices; the set of vertex ``v`` is its closed neighborhood
N[v].  Branch on the candidate covering the most undominated vertices
(ties: smallest id) — left child takes ``v`` into the dominating set, right
child discards ``v`` as a candidate (the paper: "the right branch forces v
to be out of any solution").

Bound: ``|D| + ceil(undominated / best_coverage)`` (admissible — every
further pick dominates at most ``best_coverage`` new vertices).  A node
with undominated vertices but zero possible coverage is infeasible
(INF bound, arity 0).

Fused node evaluation (DESIGN.md §1): the coverage vector (masked popcount
over closed neighborhoods), the branch vertex and the undominated count
are computed ONCE per node visit and shared between the solution test, the
bound and both children, through a pluggable ``stats_fn``:

  backend="jnp"     — inline jnp (materializes the [n, w] masked matrix);
  backend="pallas"  — ``repro.kernels.bitset_ops.domination_stats``, the
                      universal masked-popcount kernel bound with
                      mask = the undominated set and valid = the candidate
                      set (DESIGN.md §5.2/§5.4; interpret mode on the CPU only).

Both backends are bitwise-identical — same coverage counts, same
smallest-id tie-break, same bound — so the search tree is invariant under
the backend (asserted node-for-node vs the serial oracle by
``tests/test_node_eval.py``).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import INF_VALUE, BinaryProblem, NodeEval
from repro.core.serial import INF, PyNodeEval, PyProblem
from repro.problems.graphs import Graph, bit, full_mask, parse_graph_instance
from repro.registry import register_problem


class DSState(NamedTuple):
    dominated: jnp.ndarray   # uint32[w]
    cand: jnp.ndarray        # uint32[w] — vertices still allowed into D
    chosen: jnp.ndarray      # uint32[w] — current D
    size: jnp.ndarray        # int32


def _closed_adj(graph: Graph) -> np.ndarray:
    cadj = graph.adj.copy()
    for v in range(graph.n):
        cadj[v] |= bit(v, graph.words)
    return cadj


#: stats_fn contract: (dominated uint32[w], cand uint32[w]) ->
#: (best_coverage, branch_vertex, undominated) int32 scalars, where
#: coverage[v] = |N[v] \ dominated| over candidates (-1 for
#: non-candidates), best_coverage is the max (-1 when no candidate),
#: branch_vertex follows the smallest-id tie-break (0 when no candidate)
#: and undominated counts the not-yet-dominated vertices.  This is THE
#: once-per-node computation (DESIGN.md §5.4).
DomStatsFn = Callable[[jnp.ndarray, jnp.ndarray],
                      Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]


def make_domination_stats_fn(graph: Graph, backend: str = "jnp", *,
                             tile: Optional[int] = None,
                             interpret: Optional[bool] = None) -> DomStatsFn:
    """Build the per-node domination-statistics function for ``backend``.

    ``tile=None`` defers the kernel block shape to the per-shape autotuner
    (DESIGN.md §5.6)."""
    n, w = graph.n, graph.words
    cadj = jnp.asarray(_closed_adj(graph))
    fullm = jnp.asarray(full_mask(n))

    if backend == "pallas":
        from repro.kernels import ops

        def stats(dominated: jnp.ndarray, cand: jnp.ndarray):
            out = ops.domination_stats(cadj, dominated[None, :],
                                       cand[None, :], fullm, tile=tile,
                                       use_pallas=True, interpret=interpret)[0]
            # Kernel reports vertex -1 when no candidate exists; the jnp
            # argmax reports 0.  Normalize so both backends yield identical
            # (and discarded) children on dead states.
            return out[0], jnp.maximum(out[1], 0), out[2]

        return stats

    if backend != "jnp":
        raise ValueError(f"unknown dominating-set backend {backend!r}")

    word = jnp.asarray(np.arange(n, dtype=np.int32) // 32)
    shift = jnp.asarray((np.arange(n, dtype=np.int32) % 32).astype(np.uint32))
    one = jnp.uint32(1)

    def stats(dominated: jnp.ndarray, cand: jnp.ndarray):
        undom_rows = jnp.bitwise_and(cadj, jnp.bitwise_not(dominated)[None, :])
        cov = jax.lax.population_count(undom_rows).sum(axis=1).astype(
            jnp.int32)
        cand_f = ((cand[word] >> shift) & one) == one
        cov = jnp.where(cand_f, cov, jnp.int32(-1))
        rem = jnp.bitwise_and(fullm, jnp.bitwise_not(dominated))
        u = jax.lax.population_count(rem).sum().astype(jnp.int32)
        return jnp.max(cov), jnp.argmax(cov).astype(jnp.int32), u

    return stats


def _pack_ds(graph: Graph, n: int):
    """Service packing: pad into a stacked FAMILY_DS slot (closed adjacency;
    lazy import keeps problems <-> service acyclic)."""
    from repro.service.batch_problem import FAMILY_DS, pack_instance
    return pack_instance(graph, FAMILY_DS, n)


@register_problem(
    "ds",
    parse=parse_graph_instance,
    oracle=lambda graph: make_dominating_set_py(graph),
    backends=("jnp", "pallas"),
    pack=_pack_ds,
    family_id=1,                       # batch_problem.FAMILY_DS
    doc="minimum dominating set via set-cover branching (paper §V)",
)
def make_dominating_set(graph: Graph, backend: str = "jnp", *,
                        tile: Optional[int] = None,
                        interpret: Optional[bool] = None,
                        stats_fn: Optional[DomStatsFn] = None
                        ) -> BinaryProblem:
    """jnp BinaryProblem for the engine (vmap-safe, shape-static).

    ``backend`` routes the per-node coverage pass (see module docstring);
    ``stats_fn`` overrides it entirely (tests inject counting wrappers).
    Under ``backend="pallas"`` (without a ``stats_fn`` override) the
    problem also carries ``evaluate_batch``: all W lanes' coverage passes
    fuse into ONE ``domination_stats`` kernel launch per engine step
    (DESIGN.md §5.5).
    """
    n, w = graph.n, graph.words
    cadj = jnp.asarray(_closed_adj(graph))
    fullm = jnp.asarray(full_mask(n))
    one = jnp.uint32(1)
    batched = backend == "pallas" and stats_fn is None
    if stats_fn is None:
        stats_fn = make_domination_stats_fn(graph, backend, tile=tile,
                                            interpret=interpret)

    def vbit(v):
        return jnp.where(jnp.arange(w) == (v // 32),
                         one << (v.astype(jnp.uint32) % 32), jnp.uint32(0))

    def root() -> DSState:
        return DSState(dominated=jnp.zeros(w, jnp.uint32), cand=fullm,
                       chosen=jnp.zeros(w, jnp.uint32), size=jnp.int32(0))

    def _finish(state: DSState, best: jnp.ndarray, best_cov, v,
                u) -> NodeEval:
        is_sol = u == 0

        # Bound from the shared coverage maximum.
        infeasible = (u > 0) & (best_cov <= 0)
        need = (u + jnp.maximum(best_cov, 1) - 1) // jnp.maximum(best_cov, 1)
        lb = jnp.where(infeasible, INF_VALUE, state.size + need)

        # Children from the shared branch vertex.
        bv = vbit(v)
        new_cand = jnp.bitwise_and(state.cand, jnp.bitwise_not(bv))
        left = DSState(dominated=jnp.bitwise_or(state.dominated, cadj[v]),
                       cand=new_cand,
                       chosen=jnp.bitwise_or(state.chosen, bv),
                       size=state.size + 1)
        right = DSState(dominated=state.dominated, cand=new_cand,
                        chosen=state.chosen, size=state.size)
        return NodeEval(is_solution=is_sol, value=state.size, lower_bound=lb,
                        left=left, right=right, payload=state.chosen)

    def evaluate(state: DSState, best: jnp.ndarray) -> NodeEval:
        # THE one coverage pass (DESIGN.md §5.4): best |N[v] \ dominated|
        # over candidates, its vertex, and the undominated count.
        best_cov, v, u = stats_fn(state.dominated, state.cand)
        return _finish(state, best, best_cov, v, u)

    evaluate_batch = None
    if batched:
        from repro.kernels import ops

        def evaluate_batch(states: DSState, best: jnp.ndarray) -> NodeEval:
            # ONE kernel launch covers every lane's coverage pass: the
            # whole uint32[L, w] mask block is batched into each grid step
            # instead of one pallas_call per lane (DESIGN.md §5.5).
            out = ops.domination_stats(cadj, states.dominated, states.cand,
                                       fullm, tile=tile, use_pallas=True,
                                       interpret=interpret)
            return jax.vmap(_finish)(states, best, out[:, 0],
                                     jnp.maximum(out[:, 1], 0), out[:, 2])

    return BinaryProblem(
        name=f"ds[{graph.name}]", max_depth=n, root=root, evaluate=evaluate,
        payload_zero=lambda: jnp.zeros(w, jnp.uint32),
        evaluate_batch=evaluate_batch)


def make_dominating_set_py(graph: Graph) -> PyProblem:
    n, w = graph.n, graph.words
    cadj = _closed_adj(graph)
    fullm = full_mask(n)
    word = np.arange(n, dtype=np.int32) // 32
    shift = (np.arange(n, dtype=np.int32) % 32).astype(np.uint32)

    def vbit(v):
        out = np.zeros(w, np.uint32)
        out[v // 32] = np.uint32(1) << np.uint32(v % 32)
        return out

    def root():
        return (np.zeros(w, np.uint32), fullm.copy(),
                np.zeros(w, np.uint32), 0)

    def evaluate(state, best):
        dominated, cand, chosen, size = state
        cov = np.bitwise_count(cadj & ~dominated[None, :]).sum(
            axis=1).astype(np.int64)
        cand_f = ((cand[word] >> shift) & np.uint32(1)) == 1
        cov = np.where(cand_f, cov, -1)

        u = int(np.bitwise_count(fullm & ~dominated).sum())
        is_sol = u == 0

        best_cov = int(np.max(cov))
        if u > 0 and best_cov <= 0:
            lb = INF
        else:
            bc = max(best_cov, 1)
            lb = size + (u + bc - 1) // bc

        v = int(np.argmax(cov))
        bv = vbit(v)
        new_cand = cand & ~bv
        left = (dominated | cadj[v], new_cand, chosen | bv, size + 1)
        right = (dominated, new_cand, chosen, size)
        return PyNodeEval(is_sol, size, lb, left, right)

    return PyProblem(name=f"ds[{graph.name}]", max_depth=n, root=root,
                     evaluate=evaluate)
