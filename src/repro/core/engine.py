"""Vectorized recursive-backtracking engine (the paper's SERIAL-RB, SIMD-ified).

A *lane* is the TPU analogue of the paper's "core": an independent depth-first
searcher whose entire control state is the paper's ``current_idx`` array plus
a stack of search-node states along the live root-to-node path.  ``W`` lanes
advance in lockstep under ``vmap``; one *engine step* visits exactly one
search-node per active lane (one fused ``Problem.evaluate`` call — the unit
the paper's butterfly-effect analysis in §III-D counts).

Control encoding per lane (paper Fig. 2/3 semantics):

  idx[j] ∈ {UNVISITED, DELEGATED, LEFT, RIGHT} — the branch taken from depth
  ``j`` to ``j+1`` along the live path; LEFT means the right sibling at depth
  ``j+1`` is still pending, DELEGATED means it was stolen (skip on backtrack,
  Fig. 3 lines 2-3).

  depth       — current node's depth; its state is ``stack[depth]``.
  base        — the lane owns the subtree rooted at depth ``base`` (its "main
                task"); backtracking past it makes the lane idle.  Slots below
                ``base`` are the fixed path of the stolen task and are never
                donated (they belong to the chain of previous owners).

The incumbent (``best``) is shared across lanes every step — the vectorized
version of the paper's solution-broadcast notification messages.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.api import (DELEGATED, LEFT, RIGHT, UNVISITED, INF_VALUE,
                            BinaryProblem, root_of, tree_select)

PyTree = Any

#: ``Lanes.inst`` value for a lane not (yet) bound to any instance.  Such a
#: lane never steals and never donates; the service driver retargets it.
NO_INSTANCE = -1


class Lanes(NamedTuple):
    """State of W lanes on one device.  All leading dims are W unless noted.

    ``K = problem.num_instances`` instances are multiplexed over the lane
    pool: each lane serves exactly one instance (``inst``), the incumbent is
    a per-instance table, and stealing never crosses instances.  Ordinary
    single-instance problems have K = 1 and ``inst`` identically 0, which
    reduces every mechanism below to the paper's original semantics.
    """

    idx: jnp.ndarray          # int8  [W, IDX_LEN]
    depth: jnp.ndarray        # int32 [W]
    base: jnp.ndarray         # int32 [W]
    inst: jnp.ndarray         # int32 [W]   — instance the lane serves (or
                              #               NO_INSTANCE for unbound lanes)
    active: jnp.ndarray       # bool  [W]
    stack: PyTree             # leaves [W, STACK_LEN, ...]
    best: jnp.ndarray         # int32 [K]      — per-instance incumbent value
    best_payload: PyTree      # leaves [K, ...] — per-instance incumbent solution
    nodes: jnp.ndarray        # int32 [W]    — search-nodes visited
    t_s: jnp.ndarray          # int32 [W]    — tasks received (paper's T_S)
    t_r: jnp.ndarray          # int32 [W]    — task requests made (paper's T_R)
    donated: jnp.ndarray      # int32 [W]    — tasks donated
    t_c: jnp.ndarray          # int32 [W]    — tasks received CROSS-device
                              #               (a subset of t_s; telemetry
                              #               splits steal traffic by scope)
    steps: jnp.ndarray        # int32 []     — engine steps executed


def idx_len(problem: BinaryProblem) -> int:
    return problem.max_depth + 1


def stack_len(problem: BinaryProblem) -> int:
    return problem.max_depth + 2


def init_lanes(problem: BinaryProblem, num_lanes: int,
               seed_root: bool = True, bind_instance: bool = True) -> Lanes:
    """Allocate W idle lanes; optionally hand lane 0 the root task N_{0,0}.

    The paper's initialization assigns the root to C_0 and lets every other
    core request its first task through the virtual topology; here all other
    lanes start idle and are fed by the first steal rounds (bootstrap).

    ``bind_instance=False`` starts every lane UNBOUND (``inst ==
    NO_INSTANCE``): the multi-tenant service pool, where lanes only acquire
    an instance at admission/steal time and unbound lanes neither steal nor
    donate.
    """
    w, il, sl = num_lanes, idx_len(problem), stack_len(problem)
    k = problem.num_instances
    root = root_of(problem, jnp.int32(0))

    def alloc(leaf):
        buf = jnp.zeros((w, sl) + leaf.shape, leaf.dtype)
        if seed_root:
            buf = buf.at[0, 0].set(leaf)
        return buf

    stack = jax.tree_util.tree_map(alloc, root)
    active = jnp.zeros((w,), jnp.bool_)
    if seed_root:
        active = active.at[0].set(True)
    return Lanes(
        idx=jnp.full((w, il), UNVISITED, jnp.int8),
        depth=jnp.zeros((w,), jnp.int32),
        base=jnp.zeros((w,), jnp.int32),
        inst=(jnp.zeros((w,), jnp.int32) if bind_instance
              else jnp.full((w,), NO_INSTANCE, jnp.int32)),
        active=active,
        stack=stack,
        best=jnp.full((k,), INF_VALUE, jnp.int32),
        best_payload=jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (k,) + l.shape),
            problem.payload_zero()),
        nodes=jnp.zeros((w,), jnp.int32),
        t_s=jnp.zeros((w,), jnp.int32).at[0].set(1 if seed_root else 0),
        t_r=jnp.zeros((w,), jnp.int32),
        donated=jnp.zeros((w,), jnp.int32),
        t_c=jnp.zeros((w,), jnp.int32),
        steps=jnp.int32(0),
    )


def _at(n: int, pos, ndim: int) -> jnp.ndarray:
    """bool one-hot of ``pos`` along a leading axis of length ``n``,
    shaped to broadcast against an ``ndim``-dimensional array."""
    hot = jnp.arange(n, dtype=jnp.int32) == pos
    return hot.reshape((n,) + (1,) * (ndim - 1))


def read_at(s: jnp.ndarray, pos) -> jnp.ndarray:
    """``s[pos]`` along axis 0 (``pos`` in range) as a one-hot reduction.

    Per-lane reads and writes at a data-dependent depth are dense selects
    rather than dynamic indexing.  Vmapped over lanes, dynamic indexing
    becomes a gather/scatter over the lane axis.  On a TPU v5e those
    scatters cost time in proportion to the lane count, and two of them
    writing neighbouring int8 slots of one ``idx`` row lost the first
    write at 1024 lanes, so the search left the CPU's (DESIGN.md §2).
    A select over the whole axis is plain vector work."""
    hot = _at(s.shape[0], pos, s.ndim)
    if s.dtype == jnp.bool_:
        return jnp.any(hot & s, axis=0)
    return jnp.sum(jnp.where(hot, s, jnp.zeros((), s.dtype)), axis=0,
                   dtype=s.dtype)


def write_at(s: jnp.ndarray, pos, value, when=True) -> jnp.ndarray:
    """``s`` with ``s[pos] = value`` where ``when`` (``pos`` in range)."""
    return jnp.where(_at(s.shape[0], pos, s.ndim) & when,
                     jnp.asarray(value, s.dtype)[None], s)


def _select_node(idx, depth, stack):
    """Read ONE lane's current node state off its stack (vmapped by
    ``make_step`` — the select half of the select/evaluate/advance split
    that lets ``evaluate_batch`` see all lanes in one call)."""
    il = idx.shape[0]
    d = jnp.clip(depth, 0, il - 1)
    state = jax.tree_util.tree_map(lambda s: read_at(s, d), stack)
    return state, d


def _advance_lane(idx, depth, base, active, stack, best, ev, d):
    """Apply ONE lane's NodeEval: descend/backtrack and report
    (improved, value, payload) for incumbent election across lanes.

    Branchless: every path is computed and blended with ``where`` so the
    function vmaps over lanes with no divergence.  ``ev`` is the node's
    evaluation — produced per-lane by ``vmap(evaluate)`` or for all lanes
    at once by ``evaluate_batch`` (DESIGN.md §1/§5.5); either way exactly
    one evaluation backs one node visit.
    """
    il = idx.shape[0]
    c = read_at(idx, d)
    first = c == UNVISITED
    is_sol, val, lb = ev.is_solution, ev.value, ev.lower_bound

    improved = active & first & is_sol & (val < best)
    best_eff = jnp.where(improved, val, best)
    terminal = is_sol | (lb >= best_eff)

    # Which child to generate: left on first arrival, right after returning
    # from a completed left subtree.
    take_right = (~first) & (c == LEFT)
    descend = active & ((first & ~terminal) | take_right)
    child = tree_select(first, ev.left, ev.right)

    wpos = jnp.clip(d + 1, 0, il)  # stack has one extra slot
    new_stack = jax.tree_util.tree_map(
        lambda s, ch: write_at(s, wpos, ch, descend), stack, child)

    # current_idx maintenance (paper Fig. 3, line 4).
    slot_now = jnp.where(descend & first, LEFT,
                         jnp.where(descend & take_right, RIGHT, c))
    new_idx = write_at(idx, d, jnp.where(active, slot_now, c))
    # Fresh child slot starts UNVISITED.
    new_idx = write_at(new_idx, jnp.clip(d + 1, 0, il - 1), UNVISITED,
                       descend)

    new_depth = jnp.where(active, jnp.where(descend, depth + 1, depth - 1), depth)
    new_active = active & (new_depth >= base)
    new_depth = jnp.maximum(new_depth, 0)

    visited = active & first
    return (new_idx, new_depth, new_active, new_stack, visited,
            improved, jnp.where(improved, val, INF_VALUE), ev.payload)


def instance_onehot(inst: jnp.ndarray, k: int) -> jnp.ndarray:
    """bool[W, K]: lane ``l`` serves instance ``i`` — the dense form of a
    per-instance segment reduction (no scatter over the lane axis)."""
    return inst[:, None] == jnp.arange(k, dtype=inst.dtype)[None, :]


def make_step(problem: BinaryProblem):
    """Build the vectorized one-step transition Lanes -> Lanes.

    The step is select → evaluate → advance: node states are gathered per
    lane, evaluated — through ``problem.evaluate_batch`` as ONE batched
    call when the problem provides it, else ``vmap(evaluate)`` — and the
    results applied per lane.  Both evaluation paths are bitwise-identical
    by the ``evaluate_batch`` contract, so the search tree is invariant.
    """

    select_v = jax.vmap(_select_node)
    advance_v = jax.vmap(_advance_lane)
    if problem.evaluate_batch is not None:
        eval_all = problem.evaluate_batch
    else:
        eval_all = jax.vmap(problem.evaluate)

    def step(lanes: Lanes) -> Lanes:
        w = lanes.active.shape[0]
        k = lanes.best.shape[0]
        safe_inst = jnp.clip(lanes.inst, 0, k - 1)
        # Each lane prunes against ITS instance's incumbent.
        best_per_lane = lanes.best[safe_inst]
        states, d = select_v(lanes.idx, lanes.depth, lanes.stack)
        evs = eval_all(states, best_per_lane)
        (idx, depth, active, stack, visited, improved, vals,
         payloads) = advance_v(lanes.idx, lanes.depth, lanes.base,
                               lanes.active, lanes.stack, best_per_lane,
                               evs, d)
        # Incumbent election per instance (the paper's broadcast, free
        # here): segment-min of the improved values over ``inst``, then the
        # lowest-id winning lane supplies the payload for its instance.
        mine = instance_onehot(safe_inst, k)                    # [W, K]
        seg = jnp.min(jnp.where(mine, vals[:, None], INF_VALUE), axis=0)
        any_improved = seg < lanes.best
        new_best = jnp.minimum(lanes.best, seg)
        lane_ids = jnp.arange(w, dtype=jnp.int32)
        wins = mine & (improved & (vals == seg[safe_inst]))[:, None]
        winner = jnp.min(jnp.where(wins, lane_ids[:, None], w), axis=0)
        safe_winner = jnp.clip(winner, 0, w - 1)

        def elect(p, old):
            upd = any_improved.reshape((k,) + (1,) * (old.ndim - 1))
            return jnp.where(upd, p[safe_winner], old)

        new_payload = jax.tree_util.tree_map(elect, payloads,
                                             lanes.best_payload)
        return lanes._replace(
            idx=idx, depth=depth, active=active, stack=stack,
            best=new_best, best_payload=new_payload,
            nodes=lanes.nodes + visited.astype(jnp.int32),
            steps=lanes.steps + 1)

    return step


def make_expand(problem: BinaryProblem, num_steps: int,
                fused_steps: int = 1):
    """Run up to ``num_steps`` engine steps, early-exiting when all idle.

    This is the compute phase between steal rounds; ``num_steps`` is the
    round granularity R (the BSP analogue of the paper's disruption-time
    knob, hillclimbed in EXPERIMENTS.md §Perf).

    ``fused_steps`` = S > 1 fuses S step applications into each while-loop
    iteration (an unrolled ``fori_loop`` group), amortizing the loop's
    carry bookkeeping and dispatch across S node visits per launch.  Each
    fused sub-step is guarded by the exact original loop condition
    (``any(active) & step_index < num_steps``), so the sequence of actual
    ``step`` applications — and therefore the search tree, node counts and
    step counter — is IDENTICAL for every S.
    """
    step = make_step(problem)

    if fused_steps <= 1:
        def expand(lanes: Lanes) -> Lanes:
            def cond(carry):
                i, lanes = carry
                return (i < num_steps) & jnp.any(lanes.active)

            def body(carry):
                i, lanes = carry
                return i + 1, step(lanes)

            _, lanes = jax.lax.while_loop(cond, body, (jnp.int32(0), lanes))
            return lanes

        return expand

    s = int(fused_steps)

    def expand(lanes: Lanes) -> Lanes:
        def cond(carry):
            i, ln = carry
            return (i < num_steps) & jnp.any(ln.active)

        def body(carry):
            i, ln = carry

            def one(j, ln):
                run = jnp.any(ln.active) & (i + j < num_steps)
                return jax.lax.cond(run, step, lambda l: l, ln)

            return i + s, jax.lax.fori_loop(0, s, one, ln)

        _, lanes = jax.lax.while_loop(cond, body, (jnp.int32(0), lanes))
        return lanes

    return expand


def replay_path(problem: BinaryProblem, bits: jnp.ndarray,
                path_depth: jnp.ndarray, stack: PyTree,
                inst: jnp.ndarray = jnp.int32(0)) -> PyTree:
    """CONVERTINDEX: rebuild the state stack for a task index (paper §IV-A).

    Starting from the root of instance ``inst`` (plain ``root()`` for
    single-instance problems), re-applies the branch decisions ``bits[0..
    path_depth-1]`` (delegation marks already flattened to LEFT by
    FIXINDEX).  Fills ``stack[j]`` for j = 0..path_depth and returns the new
    stack.  The cost is O(D_MAX) child derivations (``Problem.apply``, i.e.
    ``evaluate`` with the non-child outputs dead-code-eliminated) — the
    paper's serial-overhead term, incurred once per received task.
    """
    il = bits.shape[0]
    root = root_of(problem, inst)
    stack = jax.tree_util.tree_map(
        lambda s, r: jax.lax.dynamic_update_index_in_dim(s, r, 0, axis=0),
        stack, root)

    def body(j, carry):
        state, stack = carry
        bit = jnp.clip(bits[j].astype(jnp.int32), 0, 1)
        nxt = problem.apply(state, bit)
        take = j < path_depth
        state = jax.tree_util.tree_map(
            lambda a, b: jnp.where(take, b, a), state, nxt)
        stack = jax.tree_util.tree_map(
            lambda s, st: jax.lax.dynamic_update_index_in_dim(
                s, jnp.where(take, st,
                             jax.lax.dynamic_index_in_dim(s, jnp.clip(j + 1, 0, s.shape[0] - 1), keepdims=False)),
                jnp.clip(j + 1, 0, s.shape[0] - 1), axis=0),
            stack, state)
        return state, stack

    _, stack = jax.lax.fori_loop(0, il, body, (root, stack))
    return stack
