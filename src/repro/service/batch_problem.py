"""Stacked multi-instance problems for the solver service.

The paper's framework turns one hard instance into thousands of tiny
indexed tasks; the service inverts the workload — MANY instances share one
lane pool.  The enabler is the same compact encoding: a lane's identity is
O(D) int8 plus one int32 instance id, so pointing a lane at a different
instance is an index swap plus CONVERTINDEX replay.

``StackedSpec`` describes K instance *slots*, each a graph padded to a
common vertex count ``n`` (padding vertices are isolated and start dead,
which provably leaves the branch-and-bound tree of the unpadded instance
untouched — every padded vertex has count -1 in the shared
coverage/degree pass, so max/argmax/bound are unchanged).  Two problem
families share the slots:

  FAMILY_VC — minimum vertex cover (``adj`` row block = adjacency);
  FAMILY_DS — minimum dominating set (``adj`` row block = CLOSED adjacency).

Both families funnel their per-node work through ONE masked-popcount pass
(DESIGN.md §1): for VC the mask is the alive set and the counts are
residual degrees; for DS the mask is the undominated set and the counts are
coverage.  The fused ``evaluate`` computes that pass once on
``tables.adj[state.inst]`` and blends the family-specific solution test,
bound, children and payload branchlessly — so a vmapped engine step over
lanes serving different tenants stays a single fused kernel.

The pass itself is backend-pluggable (``StackedSpec.bind(..., backend)``,
same seam as the single-instance problems):

  backend="jnp"     — gather ``tables.adj[inst]`` and materialize the
                      [n, w] masked matrix per lane;
  backend="pallas"  — ``repro.kernels.bitset_ops.stacked_count_stats``,
                      the batched uint32[K, n, w] variant of the universal
                      masked-popcount kernel: each lane's table block is
                      selected by instance id via scalar prefetch, so the
                      kernel never touches the other K-1 tables
                      (DESIGN.md §5.3; interpret mode on the CPU only).

``StackedTables`` is runtime DATA, not a trace-time constant: the service
driver passes it as an argument to the jitted round, so admitting a new
instance is a host-side table write with NO recompilation — under either
backend (the stacked tables are kernel *operands*, never constants).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import INF_VALUE, BinaryProblem, NodeEval, tree_select
from repro.problems.graphs import Graph, full_mask, num_words

FAMILY_VC = 0
FAMILY_DS = 1

#: Kernel backends the stacked shared-evaluate accepts (``StackedSpec.bind``)
#: — the service-side capability surface (DESIGN.md §5.3/§6).
STACKED_BACKENDS = ("jnp", "pallas")


class StackedTables(NamedTuple):
    """Per-slot instance data (leaves are device arrays inside the jit)."""

    adj: jnp.ndarray      # uint32[K, n, w] — adjacency (vc) / closed adj (ds)
    fullm: jnp.ndarray    # uint32[K, w]    — the slot's real-vertex mask
    family: jnp.ndarray   # int32[K]        — FAMILY_VC | FAMILY_DS


class SvcState(NamedTuple):
    """Union state: (a, b, c) mean (alive, cover, -) for VC and
    (dominated, cand, chosen) for DS.  ``inst`` rides in the state so that
    ``evaluate`` can index the stacked tables without an engine-protocol
    change; ``Lanes.inst`` is the engine-side authority and the two are
    kept equal by construction (roots embed it, children inherit it)."""

    inst: jnp.ndarray     # int32 []
    a: jnp.ndarray        # uint32[w]
    b: jnp.ndarray        # uint32[w]
    c: jnp.ndarray        # uint32[w]
    size: jnp.ndarray     # int32 []


def pack_instance(graph: Graph, family: int, n: int
                  ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pad one instance to ``n`` vertices: (adj[n, w], fullm[w], family).

    For FAMILY_DS the row block is the CLOSED adjacency (N[v]), matching
    ``repro.problems.dominating_set``.
    """
    if graph.n > n:
        raise ValueError(f"instance n={graph.n} exceeds slot size n={n}")
    w = num_words(n)
    adj = np.zeros((n, w), np.uint32)
    adj[:graph.n, :graph.words] = graph.adj
    if family == FAMILY_DS:
        for v in range(graph.n):
            adj[v, v // 32] |= np.uint32(1) << np.uint32(v % 32)
    elif family != FAMILY_VC:
        raise ValueError(f"unknown family {family!r}")
    fm = np.zeros(w, np.uint32)
    fm[:graph.words] = full_mask(graph.n)
    return adj, fm, family


@dataclasses.dataclass(frozen=True)
class StackedSpec:
    """Static shape of a service deployment: K slots of up-to-n vertices."""

    n: int          # padded vertex count (max instance size)
    k: int          # instance slots multiplexed over the lane pool

    @property
    def words(self) -> int:
        return num_words(self.n)

    def empty_tables(self) -> StackedTables:
        """Host-side numpy tables with every slot free (edgeless VC —
        instantly solved if ever seeded, but free slots are never seeded)."""
        return StackedTables(
            adj=np.zeros((self.k, self.n, self.words), np.uint32),
            fullm=np.zeros((self.k, self.words), np.uint32),
            family=np.zeros((self.k,), np.int32))

    def bind(self, tables: StackedTables, backend: str = "jnp", *,
             tile: Optional[int] = None,
             interpret: Optional[bool] = None) -> BinaryProblem:
        """Build the K-instance BinaryProblem over (possibly traced) tables.

        ``backend`` routes the shared masked-popcount pass (see module
        docstring) — "jnp" or "pallas"; both are NodeEval-identical.
        Under "pallas" the problem also carries ``evaluate_batch``, the
        fused-round fast path: all W lanes' masked-popcount passes become
        ONE ``stacked_count_stats`` launch per engine step (DESIGN.md
        §5.5).  ``tile=None`` defers the block shape to the per-shape
        autotuner (DESIGN.md §5.6).
        """
        n, w, k = self.n, self.words, self.k
        word = jnp.asarray(np.arange(n, dtype=np.int32) // 32)
        shift = jnp.asarray((np.arange(n, dtype=np.int32) % 32)
                            .astype(np.uint32))
        one = jnp.uint32(1)
        zero_mask = jnp.zeros((w,), jnp.uint32)

        if backend == "pallas":
            from repro.kernels import ops

            def shared_stats(i, mask, validm, undom):
                # undom is recomputed by the kernel as the pass's mask
                # popcount (== |undominated| for DS lanes, whose mask IS
                # the undominated set; VC lanes never consume it).
                out = ops.stacked_count_stats(
                    tables.adj, i[None], mask[None, :], validm[None, :],
                    tile=tile, use_pallas=True, interpret=interpret)[0]
                return out[0], jnp.maximum(out[1], 0), out[2], out[3]
        elif backend == "jnp":
            def shared_stats(i, mask, validm, undom):
                rows = jnp.bitwise_and(tables.adj[i], mask[None, :])
                cnt = jax.lax.population_count(rows).sum(axis=1).astype(
                    jnp.int32)
                valid_f = ((validm[word] >> shift) & one) == one
                cnt = jnp.where(valid_f, cnt, jnp.int32(-1))
                u = jax.lax.population_count(undom).sum().astype(jnp.int32)
                return (jnp.max(cnt), jnp.argmax(cnt).astype(jnp.int32),
                        jnp.sum(jnp.maximum(cnt, 0)), u)
        else:
            raise ValueError(f"unknown stacked-service backend {backend!r}")

        def vbit(v):
            return jnp.where(jnp.arange(w) == (v // 32),
                             one << (v.astype(jnp.uint32) % 32),
                             jnp.uint32(0))

        def instance_root(inst) -> SvcState:
            i = jnp.clip(jnp.asarray(inst, jnp.int32), 0, k - 1)
            is_vc = tables.family[i] == FAMILY_VC
            fm = tables.fullm[i]
            return SvcState(
                inst=jnp.asarray(inst, jnp.int32),
                a=jnp.where(is_vc, fm, zero_mask),   # alive / dominated
                b=jnp.where(is_vc, zero_mask, fm),   # cover / cand
                c=zero_mask,
                size=jnp.int32(0))

        def _stats_inputs(state: SvcState):
            """The shared pass's operands, per lane (clipped instance id —
            idle lanes evaluate against slot 0 and are discarded, so the
            scalar and batched paths agree bitwise).

            VC: mask = alive set       → counts = residual degrees.
            DS: mask = undominated set → counts = coverage |N[v] \\ dom|.
            """
            i = jnp.clip(state.inst, 0, k - 1)
            is_vc = tables.family[i] == FAMILY_VC
            undom = jnp.bitwise_and(tables.fullm[i],
                                    jnp.bitwise_not(state.a))
            mask = jnp.where(is_vc, state.a, undom)
            validm = jnp.where(is_vc, state.a, state.b)   # alive / candidates
            return i, mask, validm, undom

        def _finish(state: SvcState, best: jnp.ndarray, cmax, v, csum,
                    u) -> NodeEval:
            """Everything after the shared pass: family-specific solution
            test, admissible bound, and both children."""
            i = jnp.clip(state.inst, 0, k - 1)
            is_vc = tables.family[i] == FAMILY_VC
            vc_sol = cmax <= 0
            d_eff = jnp.maximum(cmax, 1)
            vc_lb = state.size + (csum + 2 * d_eff - 1) // (2 * d_eff)

            ds_sol = u == 0
            infeasible = (u > 0) & (cmax <= 0)
            bc = jnp.maximum(cmax, 1)
            ds_lb = jnp.where(infeasible, INF_VALUE,
                              state.size + (u + bc - 1) // bc)

            # Children from the shared branch vertex.
            bv = vbit(v)
            row_v = tables.adj[i, v]
            nb = jnp.bitwise_and(row_v, state.a)          # vc: alive N(v)
            nb_count = jax.lax.population_count(nb).sum().astype(jnp.int32)
            new_cand = jnp.bitwise_and(state.b, jnp.bitwise_not(bv))

            vc_left = SvcState(
                inst=state.inst,
                a=jnp.bitwise_and(state.a, jnp.bitwise_not(bv)),
                b=jnp.bitwise_or(state.b, bv), c=state.c,
                size=state.size + 1)
            vc_right = SvcState(
                inst=state.inst,
                a=jnp.bitwise_and(state.a,
                                  jnp.bitwise_not(jnp.bitwise_or(nb, bv))),
                b=jnp.bitwise_or(state.b, nb), c=state.c,
                size=state.size + nb_count)
            ds_left = SvcState(
                inst=state.inst,
                a=jnp.bitwise_or(state.a, row_v),
                b=new_cand,
                c=jnp.bitwise_or(state.c, bv),
                size=state.size + 1)
            ds_right = SvcState(
                inst=state.inst, a=state.a, b=new_cand, c=state.c,
                size=state.size)

            return NodeEval(
                is_solution=jnp.where(is_vc, vc_sol, ds_sol),
                value=state.size,
                lower_bound=jnp.where(is_vc, vc_lb, ds_lb),
                left=tree_select(is_vc, vc_left, ds_left),
                right=tree_select(is_vc, vc_right, ds_right),
                payload=jnp.where(is_vc, state.b, state.c))

        def evaluate(state: SvcState, best: jnp.ndarray) -> NodeEval:
            i, mask, validm, undom = _stats_inputs(state)
            cmax, v, csum, u = shared_stats(i, mask, validm, undom)
            return _finish(state, best, cmax, v, csum, u)

        evaluate_batch = None
        if backend == "pallas":
            def evaluate_batch(states: SvcState,
                               best: jnp.ndarray) -> NodeEval:
                # ONE kernel launch covers every lane's shared pass: the
                # stacked kernel batches the whole [L, w] mask block into
                # each grid step instead of one pallas_call per lane.
                i, mask, validm, _ = jax.vmap(_stats_inputs)(states)
                out = ops.stacked_count_stats(
                    tables.adj, i, mask, validm, tile=tile,
                    use_pallas=True, interpret=interpret)
                return jax.vmap(_finish)(
                    states, best, out[:, 0], jnp.maximum(out[:, 1], 0),
                    out[:, 2], out[:, 3])

        return BinaryProblem(
            name=f"stacked[k={k},n={n}]",
            max_depth=n,
            root=lambda: instance_root(jnp.int32(0)),
            evaluate=evaluate,
            payload_zero=lambda: jnp.zeros((w,), jnp.uint32),
            num_instances=k,
            instance_root=instance_root,
            evaluate_batch=evaluate_batch,
        )
