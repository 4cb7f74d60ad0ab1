"""VERTEX COVER by max-degree branching (paper §V).

Branching rule (the paper's): deterministically select an alive vertex ``v``
of maximum degree (ties: smallest id).  Left child adds ``v`` to the cover;
right child adds *all* alive neighbors N(v) to the cover.  Either ``v`` or
all of N(v) is in any cover, so the rule is complete; each child removes at
least one vertex so the tree depth is at most n.

Bound: ``|cover| + ceil(m_alive / Δ_alive)`` — each additional cover vertex
removes at most Δ edges, an admissible lower bound (branch-and-reduce
pruning, §I).  The incumbent broadcast makes this bound global, which is
the mechanism behind the paper's super-linear speedups on the 60-cell.

State is two packed bitsets + a counter; see ``repro.problems.graphs``.

Fused node evaluation (DESIGN.md §1/§3).  Every per-node quantity here —
the solution test (residual graph edgeless), the bound (Δ and 2·m of the
residual graph) and the branch vertex (argmax degree) — is a function of
ONE masked-popcount degree pass over the adjacency bitsets.  The fused
``evaluate`` performs that pass exactly once per node visit, through a
pluggable ``stats_fn``:

  backend="jnp"     — inline jnp (materializes the [n, w] masked matrix);
  backend="pallas"  — ``repro.kernels.bitset_degree.degree_stats``, the
                      universal masked-popcount kernel of
                      ``repro.kernels.bitset_ops`` bound with mask = valid
                      = the alive set (DESIGN.md §5.2/§5.4; interpret
                      mode on the CPU only); vmap over lanes lifts into
                      an extra grid dimension.

Both backends are bitwise-identical (same degrees, same smallest-id
tie-break, same bound), so the search tree is invariant under the backend —
asserted against the serial oracle node-for-node by tests.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import BinaryProblem, NodeEval
from repro.core.serial import PyNodeEval, PyProblem
from repro.problems.graphs import Graph, full_mask, parse_graph_instance
from repro.registry import register_problem


class VCState(NamedTuple):
    alive: jnp.ndarray    # uint32[w] — vertices still in the residual graph
    cover: jnp.ndarray    # uint32[w] — vertices chosen into the cover
    size: jnp.ndarray     # int32     — |cover|


def _vertex_bits(n: int):
    word = np.arange(n, dtype=np.int32) // 32
    shift = (np.arange(n, dtype=np.int32) % 32).astype(np.uint32)
    return word, shift


#: stats_fn contract: alive uint32[w] -> (max_degree, branch_vertex,
#: degree_sum) int32 scalars, where degrees are over the residual graph,
#: max_degree is -1 when no vertex is alive, branch_vertex follows the
#: smallest-id tie-break (0 when nothing is alive) and degree_sum is
#: 2 * m_alive.  This is THE once-per-node computation.
StatsFn = Callable[[jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]


def make_degree_stats_fn(graph: Graph, backend: str = "jnp", *,
                         tile: Optional[int] = None,
                         interpret: Optional[bool] = None) -> StatsFn:
    """Build the per-node degree-statistics function for ``backend``.

    ``tile=None`` defers the kernel block shape to the per-shape autotuner
    (DESIGN.md §5.6)."""
    n, w = graph.n, graph.words
    adj = jnp.asarray(graph.adj)                      # uint32[n, w]

    if backend == "pallas":
        from repro.kernels import ops

        def stats(alive: jnp.ndarray):
            out = ops.degree_stats(adj, alive[None, :], tile=tile,
                                   use_pallas=True, interpret=interpret)[0]
            # Kernel reports vertex -1 when nothing is alive; the jnp argmax
            # reports 0.  Normalize so both backends yield identical (and
            # discarded) children on dead states.
            return out[0], jnp.maximum(out[1], 0), out[2]

        return stats

    if backend != "jnp":
        raise ValueError(f"unknown vertex-cover backend {backend!r}")

    word_np, shift_np = _vertex_bits(n)
    word, shift = jnp.asarray(word_np), jnp.asarray(shift_np)
    one = jnp.uint32(1)

    def stats(alive: jnp.ndarray):
        rows = jnp.bitwise_and(adj, alive[None, :])
        degs = jax.lax.population_count(rows).sum(axis=1).astype(jnp.int32)
        alive_f = ((alive[word] >> shift) & one) == one
        degs = jnp.where(alive_f, degs, jnp.int32(-1))
        return (jnp.max(degs), jnp.argmax(degs).astype(jnp.int32),
                jnp.sum(jnp.maximum(degs, 0)))

    return stats


def _pack_vc(graph: Graph, n: int):
    """Service packing: pad into a stacked FAMILY_VC slot (lazy import keeps
    problems <-> service acyclic)."""
    from repro.service.batch_problem import FAMILY_VC, pack_instance
    return pack_instance(graph, FAMILY_VC, n)


@register_problem(
    "vc",
    parse=parse_graph_instance,
    oracle=lambda graph: make_vertex_cover_py(graph),
    backends=("jnp", "pallas"),
    pack=_pack_vc,
    family_id=0,                       # batch_problem.FAMILY_VC
    doc="minimum vertex cover by max-degree branching (paper §V)",
)
def make_vertex_cover(graph: Graph, backend: str = "jnp", *,
                      tile: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      stats_fn: Optional[StatsFn] = None) -> BinaryProblem:
    """jnp BinaryProblem for the engine (vmap-safe, shape-static).

    ``backend`` routes the per-node degree pass (see module docstring);
    ``stats_fn`` overrides it entirely (tests inject counting wrappers).
    Under ``backend="pallas"`` (without a ``stats_fn`` override) the
    problem also carries ``evaluate_batch``: all W lanes' degree passes
    fuse into ONE ``degree_stats`` kernel launch per engine step
    (DESIGN.md §5.5).
    """
    n, w = graph.n, graph.words
    adj = jnp.asarray(graph.adj)
    one = jnp.uint32(1)
    fullm = jnp.asarray(full_mask(n))
    batched = backend == "pallas" and stats_fn is None
    if stats_fn is None:
        stats_fn = make_degree_stats_fn(graph, backend, tile=tile,
                                        interpret=interpret)

    def vbit(v):                                      # uint32[w], bit v
        return jnp.where(jnp.arange(w) == (v // 32),
                         one << (v.astype(jnp.uint32) % 32),
                         jnp.uint32(0))

    def root() -> VCState:
        return VCState(alive=fullm, cover=jnp.zeros(w, jnp.uint32),
                       size=jnp.int32(0))

    def _finish(state: VCState, best: jnp.ndarray, dmax, v,
                m2) -> NodeEval:
        # Solution test: the residual graph has no edges left.
        edgeless = dmax <= 0

        # Bound: |cover| + ceil(m_alive / Δ_alive).
        d_eff = jnp.maximum(dmax, 1)
        need = (m2 + 2 * d_eff - 1) // (2 * d_eff)    # ceil(m / Δ)
        lb = state.size + need

        # Children from the shared branch vertex.
        bv = vbit(v)
        nb = jnp.bitwise_and(adj[v], state.alive)     # alive neighborhood
        nb_count = jax.lax.population_count(nb).sum().astype(jnp.int32)
        left = VCState(
            alive=jnp.bitwise_and(state.alive, jnp.bitwise_not(bv)),
            cover=jnp.bitwise_or(state.cover, bv),
            size=state.size + 1)
        right = VCState(
            alive=jnp.bitwise_and(state.alive,
                                  jnp.bitwise_not(jnp.bitwise_or(nb, bv))),
            cover=jnp.bitwise_or(state.cover, nb),
            size=state.size + nb_count)
        return NodeEval(is_solution=edgeless, value=state.size,
                        lower_bound=lb, left=left, right=right,
                        payload=state.cover)

    def evaluate(state: VCState, best: jnp.ndarray) -> NodeEval:
        dmax, v, m2 = stats_fn(state.alive)           # the ONE degree pass
        return _finish(state, best, dmax, v, m2)

    evaluate_batch = None
    if batched:
        from repro.kernels import ops

        def evaluate_batch(states: VCState, best: jnp.ndarray) -> NodeEval:
            # ONE kernel launch covers every lane's degree pass: the whole
            # uint32[L, w] alive block is batched into each grid step
            # instead of one pallas_call per lane (DESIGN.md §5.5).
            out = ops.degree_stats(adj, states.alive, tile=tile,
                                   use_pallas=True, interpret=interpret)
            return jax.vmap(_finish)(states, best, out[:, 0],
                                     jnp.maximum(out[:, 1], 0), out[:, 2])

    return BinaryProblem(
        name=f"vc[{graph.name}]",
        max_depth=n,
        root=root,
        evaluate=evaluate,
        payload_zero=lambda: jnp.zeros(w, jnp.uint32),
        evaluate_batch=evaluate_batch,
    )


def make_vertex_cover_callbacks(graph: Graph, *,
                                degrees_counter: Optional[dict] = None
                                ) -> BinaryProblem:
    """The PRE-fusion three-callback form, kept as the legacy/adapter
    baseline: ``leaf_value``, ``lower_bound`` and ``apply`` each recompute
    the full degree vector, so one node visit pays ~4 degree passes.
    ``degrees_counter["n"]`` (if given) counts those passes — benchmarks
    and the fusion tests measure the win against this.
    """
    n, w = graph.n, graph.words
    adj = jnp.asarray(graph.adj)
    word_np, shift_np = _vertex_bits(n)
    word, shift = jnp.asarray(word_np), jnp.asarray(shift_np)
    one = jnp.uint32(1)
    fullm = jnp.asarray(full_mask(n))

    def degrees(alive):                               # int32[n], -1 for dead
        if degrees_counter is not None:
            degrees_counter["n"] = degrees_counter.get("n", 0) + 1
        rows = jnp.bitwise_and(adj, alive[None, :])
        degs = jax.lax.population_count(rows).sum(axis=1).astype(jnp.int32)
        alive_f = ((alive[word] >> shift) & one) == one
        return jnp.where(alive_f, degs, jnp.int32(-1))

    def vbit(v):
        return jnp.where(jnp.arange(w) == (v // 32),
                         one << (v.astype(jnp.uint32) % 32),
                         jnp.uint32(0))

    def root() -> VCState:
        return VCState(alive=fullm, cover=jnp.zeros(w, jnp.uint32),
                       size=jnp.int32(0))

    def apply(state: VCState, bit: jnp.ndarray) -> VCState:
        v = jnp.argmax(degrees(state.alive)).astype(jnp.int32)
        bv = vbit(v)
        nb = jnp.bitwise_and(adj[v], state.alive)
        nb_count = jax.lax.population_count(nb).sum().astype(jnp.int32)
        take_v = bit == 0
        dead = jnp.where(take_v, bv, jnp.bitwise_or(nb, bv))
        added = jnp.where(take_v, bv, nb)
        return VCState(
            alive=jnp.bitwise_and(state.alive, jnp.bitwise_not(dead)),
            cover=jnp.bitwise_or(state.cover, added),
            size=state.size + jnp.where(take_v, jnp.int32(1), nb_count))

    def leaf_value(state: VCState) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return jnp.max(degrees(state.alive)) <= 0, state.size

    def lower_bound(state: VCState) -> jnp.ndarray:
        degs = degrees(state.alive)
        dmax = jnp.maximum(jnp.max(degs), 1)
        m2 = jnp.sum(jnp.maximum(degs, 0))            # 2 * m_alive
        need = (m2 + 2 * dmax - 1) // (2 * dmax)      # ceil(m / Δ)
        return state.size + need

    return BinaryProblem.from_callbacks(
        name=f"vc[{graph.name}]",
        max_depth=n,
        root=root,
        apply=apply,
        leaf_value=leaf_value,
        lower_bound=lower_bound,
        solution_payload=lambda s: s.cover,
        payload_zero=lambda: jnp.zeros(w, jnp.uint32),
    )


def make_vertex_cover_py(graph: Graph) -> PyProblem:
    """numpy scalar mirror — must branch identically to the jnp form.

    Fused like the jnp form: one degree pass per ``evaluate``.
    """
    n, w = graph.n, graph.words
    adj = graph.adj
    word_np, shift_np = _vertex_bits(n)
    fullm = full_mask(n)

    def degrees(alive):
        degs = np.bitwise_count(adj & alive[None, :]).sum(axis=1).astype(np.int64)
        alive_f = ((alive[word_np] >> shift_np) & np.uint32(1)) == 1
        return np.where(alive_f, degs, -1)

    def vbit(v):
        out = np.zeros(w, np.uint32)
        out[v // 32] = np.uint32(1) << np.uint32(v % 32)
        return out

    def root():
        return (fullm.copy(), np.zeros(w, np.uint32), 0)

    def evaluate(state, best):
        alive, cover, size = state
        degs = degrees(alive)                         # the ONE degree pass
        dmax = int(np.max(degs))
        edgeless = dmax <= 0

        d_eff = max(dmax, 1)
        m2 = int(np.maximum(degs, 0).sum())
        lb = size + (m2 + 2 * d_eff - 1) // (2 * d_eff)

        v = int(np.argmax(degs))
        bv = vbit(v)
        nb = adj[v] & alive
        left = (alive & ~bv, cover | bv, size + 1)
        right = (alive & ~(nb | bv), cover | nb,
                 size + int(np.bitwise_count(nb).sum()))
        return PyNodeEval(edgeless, size, lb, left, right)

    return PyProblem(name=f"vc[{graph.name}]", max_depth=n, root=root,
                     evaluate=evaluate)
