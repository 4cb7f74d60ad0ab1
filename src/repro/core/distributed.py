"""Distributed solve: shard_map steal rounds across the device mesh.

The paper's decentralized MPI protocol (virtual parent topology, non-blocking
task requests, incumbent broadcast, 3-state termination) maps to
bulk-synchronous rounds on a TPU mesh (DESIGN.md §2):

  round := expand(R engine steps)            # pure lane-local compute
           → intra-device steal              # lanes balance within a chip
           → cross-device steal              # collectives over the mesh
           → incumbent all-reduce(min)       # paper's notification broadcast
           → termination all-reduce          # paper's 3-state protocol

Cross-device steal (deterministic, loss-free):

  1. every device advertises (idle_count, donatable_count) — all_gather;
  2. a greedy prefix quota assigns each device a donation count such that
     Σ donate_i ≤ Σ idle_i (no extracted task can go unclaimed — extraction
     marks the donor slot DELEGATED, so an unclaimed task would be a lost
     subtree; the quota rule makes claiming a bijection);
  3. devices extract their quota (heaviest first) and all_gather the index
     vectors — O(d) int8 each, the paper's compact task encoding is what
     makes this affordable at 512+ devices;
  4. device r's idle lanes claim the tasks whose global rank matches their
     global thief rank (pure arithmetic, no extra messages);
  5. psum-min of the incumbent; the round loop ends when the global number
     of active lanes and donatable tasks are both zero.

The host driver (``repro.solver.Solver.solve``) runs these jitted rounds in
a Python loop so that checkpointing (paper §VII: persist ``current_idx``),
elastic re-sharding and fault injection happen at round boundaries — the
production posture for restartable long jobs.  The kwarg-style ``solve``
kept here is a deprecated shim over that facade (DESIGN.md §6).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.api import UNVISITED, BinaryProblem
from repro.core import steal
from repro.core.engine import (Lanes, init_lanes, instance_onehot,
                               make_expand)


class SolveStats(NamedTuple):
    best: int
    rounds: int
    nodes: int
    t_s: int           # total tasks received (paper's T_S numerator)
    t_r: int           # total task requests (paper's T_R numerator)
    donated: int
    lanes: int
    t_c: int = 0       # tasks received cross-device (subset of t_s)


def _axis_rank(axis_names: Sequence[str]) -> jnp.ndarray:
    """Linearized device rank over (possibly multiple) mesh axes."""
    rank = jnp.int32(0)
    for name in axis_names:
        rank = rank * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return rank


def cross_device_steal(problem: BinaryProblem, lanes: Lanes,
                       axis_names: Sequence[str], max_ship: int) -> Lanes:
    """One cross-device steal phase (steps 1-4 above), instance-scoped.

    ``max_ship`` bounds tasks shipped per device per round (static shape of
    the all_gather payload).  With K > 1 instances the entire protocol runs
    PER INSTANCE: demand/supply summaries, greedy prefix quotas and the
    rank-arithmetic claim are all keyed by ``inst``, so a thief only ever
    claims a task of its own instance (the tenant-isolation invariant).
    K = 1 reduces to the original single-pool protocol.
    """
    w, il = lanes.idx.shape
    k = lanes.best.shape[0]
    ax = tuple(axis_names)
    me = _axis_rank(ax)
    lane_ids = jnp.arange(w, dtype=jnp.int32)
    safe_inst = jnp.clip(lanes.inst, 0, k - 1)

    thieves = steal.thief_mask(lanes)
    slots = steal.donor_slots(lanes)
    donors = steal.donor_mask(lanes, slots)
    mine = instance_onehot(safe_inst, k)                        # [W, K]
    demand_local = jnp.sum(mine & thieves[:, None], axis=0,
                           dtype=jnp.int32)
    donatable = jnp.sum(mine & donors[:, None], axis=0, dtype=jnp.int32)

    # (1) advertise; all_gather along the flattened mesh axes.
    summary = jnp.stack([demand_local, donatable], axis=1)      # [K, 2]
    all_sum = jax.lax.all_gather(summary, ax, tiled=False).reshape(-1, k, 2)
    demands, supplies = all_sum[:, :, 0], all_sum[:, :, 1]      # [D, K]
    total_demand = jnp.sum(demands, axis=0)                     # [K]

    # (2) greedy prefix quota per instance: devices donate in rank order
    # until that instance's demand is met.
    presum = jnp.cumsum(supplies, axis=0) - supplies            # [D, K]
    quota = jnp.clip(total_demand[None, :]
                     - jnp.minimum(presum, total_demand[None, :]),
                     0, supplies)                               # [D, K]
    # Cap each device's TOTAL at max_ship (static payload) with an
    # instance-major prefix over the demand-limited quotas — capping the
    # quotas (not the donatable counts) so a zero-demand instance's idle
    # supply can never crowd higher-id tenants out of the budget.  Every
    # device computes the same capped matrix, keeping the rank arithmetic
    # below globally consistent.
    qpre = jnp.cumsum(quota, axis=1) - quota                    # [D, K]
    quota = jnp.clip(max_ship - jnp.minimum(qpre, max_ship), 0, quota)
    my_quota = quota[me]                                        # [K]

    lanes, bits, tdepth, tinst, trank, valid = steal.extract_tasks(
        lanes, my_quota, max_tasks=max_ship)

    # (3) ship the index vectors (tiny: max_ship × (IDX_LEN+4) int32).
    # Each row carries its GLOBAL within-instance rank so claiming needs no
    # further coordination.
    task_offset = jnp.cumsum(quota, axis=0) - quota             # [D, K]
    grank_task = task_offset[me, tinst] + trank
    payload = jnp.concatenate(
        [bits.astype(jnp.int32), tdepth[:, None], tinst[:, None],
         grank_task[:, None], valid[:, None].astype(jnp.int32)],
        axis=1)                                                 # [S, IL+4]
    world = jax.lax.all_gather(payload, ax, tiled=False).reshape(
        -1, il + 4)                                             # [D*S, IL+4]
    w_bits, w_depth = world[:, :il], world[:, il]
    w_inst, w_grank = world[:, il + 1], world[:, il + 2]
    w_valid = world[:, il + 3] > 0

    # (4) claim by per-instance global rank arithmetic: the thief with
    # within-instance global rank g claims the instance's g-th global task.
    thief_offset = (jnp.cumsum(demands, axis=0) - demands)[me]  # [K]
    my_trank = steal._rank_within_instance(thieves, lane_ids, lanes.inst)
    my_grank = thief_offset[safe_inst] + my_trank
    src, claim = steal.claim_tasks(thieves, safe_inst, my_grank,
                                   w_inst, w_grank, w_valid)

    rbits = jnp.where(claim[:, None], w_bits[src].astype(jnp.int8),
                      UNVISITED)
    rdepth = jnp.where(claim, w_depth[src], 0)
    rinst = jnp.where(claim, w_inst[src], 0)

    lanes = lanes._replace(t_r=lanes.t_r + thieves.astype(jnp.int32))
    return steal.install_tasks(problem, lanes, rbits, rdepth, rinst, claim,
                               cross=True)


def make_round(problem: BinaryProblem, steps_per_round: int,
               axis_names: Sequence[str] = (), max_ship: int = 16,
               fused_steps: int = 1,
               ) -> Callable[[Lanes], Tuple[Lanes, jnp.ndarray]]:
    """Build the per-device round body (expand → steal → share → count).

    With empty ``axis_names`` this is the single-device round used by unit
    tests; otherwise it must run inside shard_map over those axes.
    ``fused_steps`` groups S engine steps per expand-loop iteration
    (tree-identical for any S — see ``make_expand``).
    """
    expand = make_expand(problem, steps_per_round, fused_steps)

    def round_fn(lanes: Lanes) -> Tuple[Lanes, jnp.ndarray]:
        lanes = expand(lanes)
        lanes = steal.balance_device(problem, lanes)
        if axis_names:
            lanes = cross_device_steal(problem, lanes, axis_names, max_ship)
            # Paper's notification broadcast: share the incumbent table.
            best = jax.lax.pmin(lanes.best, tuple(axis_names))
            lanes = lanes._replace(best=best)
        # Termination metric PER INSTANCE: active lanes + donatable slots.
        # The service driver retires instance i when open_work[i] == 0; the
        # single-instance solve sums the vector.
        k = lanes.best.shape[0]
        safe_inst = jnp.clip(lanes.inst, 0, k - 1)
        slots = steal.donor_slots(lanes)
        contrib = (lanes.active.astype(jnp.int32)
                   + (lanes.active
                      & (slots < lanes.idx.shape[1])).astype(jnp.int32))
        open_work = jnp.sum(jnp.where(instance_onehot(safe_inst, k),
                                      contrib[:, None], 0), axis=0)
        if axis_names:
            open_work = jax.lax.psum(open_work, tuple(axis_names))
        return lanes, open_work

    return round_fn


def lane_partition_specs(problem: BinaryProblem,
                         axis_names: Sequence[str]) -> Lanes:
    """PartitionSpec pytree for ``Lanes`` under a mesh: lane arrays shard
    their leading W-dim over all mesh axes; the per-instance incumbent
    table (``best``, ``best_payload``) and the step clock are replicated.
    Shared by the solve path, the sharded service driver and the mesh
    tests."""
    axes = tuple(axis_names)

    def spec_for(field):
        return P() if field in ("best", "steps", "best_payload") else P(axes)

    proto = _lanes_proto(problem)
    return Lanes(**{f: jax.tree_util.tree_map(
        lambda _: spec_for(f), getattr(proto, f)) for f in Lanes._fields})


def make_distributed_round(problem: BinaryProblem, mesh: Mesh,
                           steps_per_round: int, max_ship: int = 16,
                           fused_steps: int = 1):
    """shard_map the round over every axis of ``mesh`` (flat worker pool)."""
    axes = tuple(mesh.axis_names)
    round_fn = make_round(problem, steps_per_round, axes, max_ship,
                          fused_steps)
    in_specs = lane_partition_specs(problem, axes)
    fn = jax.shard_map(round_fn, mesh=mesh, in_specs=(in_specs,),
                       out_specs=(in_specs, P()), check_vma=False)
    return jax.jit(fn)


def _lanes_proto(problem: BinaryProblem) -> Lanes:
    """Structure-only prototype used to build PartitionSpec pytrees."""
    return init_lanes(problem, 1, seed_root=False)


def solve(problem: BinaryProblem,
          num_lanes: int,
          steps_per_round: int = 256,
          max_rounds: int = 100000,
          mesh: Optional[Mesh] = None,
          max_ship: int = 16,
          bootstrap_rounds: int = 0,
          bootstrap_steps: int = 8,
          checkpoint_every: int = 0,
          checkpoint_path: Optional[str] = None,
          resume_from: Optional[str] = None,
          on_round: Optional[Callable[[int, Lanes, int], None]] = None,
          ) -> Tuple[Any, SolveStats, Lanes]:
    """DEPRECATED kwarg entry point — use :class:`repro.solver.Solver`.

    Thin shim over ``Solver(SolverConfig(...)).solve(problem)`` (DESIGN.md
    §6); the round loop is the facade's, so results are bitwise-identical
    to the new API.  ``num_lanes`` is the per-device lane count
    (``SolverConfig.lanes``); ``on_round`` maps onto the typed
    :class:`repro.solver.ProgressEvent` stream ("round" events).
    """
    import warnings

    from repro.solver import ProgressEvent, Solver, SolverConfig

    warnings.warn(
        "repro.core.distributed.solve(...) is deprecated; use "
        "repro.solver.Solver(SolverConfig(...)).solve(problem)",
        DeprecationWarning, stacklevel=2)
    if checkpoint_every and not checkpoint_path:
        checkpoint_every = 0        # legacy behavior: silently no-op
    config = SolverConfig(
        lanes=num_lanes, steps_per_round=steps_per_round,
        max_rounds=max_rounds, mesh=mesh, max_ship=max_ship,
        bootstrap_rounds=bootstrap_rounds, bootstrap_steps=bootstrap_steps,
        checkpoint_every=checkpoint_every, checkpoint_path=checkpoint_path,
        resume_from=resume_from)
    on_event = None
    if on_round is not None:
        def on_event(ev: ProgressEvent) -> None:
            if ev.kind == "round":
                on_round(ev.round, ev.lanes, ev.open_work)
    result = Solver(config, on_event=on_event).solve(problem)
    return result.payload, result.stats, result.lanes


def _gather_lanes(lanes: Lanes) -> Lanes:
    """Pull lane state to host (fully addressable) for pool/ckpt surgery."""
    return jax.tree_util.tree_map(
        lambda l: jnp.asarray(np.asarray(jax.device_get(l))), lanes)


def _shard_lanes(lanes: Lanes, mesh: Mesh) -> Lanes:
    """Place lane arrays sharded over all mesh axes (leading dim)."""
    axes = tuple(mesh.axis_names)

    def put(field, leaf):
        if field in ("best", "steps") or leaf.ndim == 0:
            spec = P()
        elif field == "best_payload":
            spec = P()
        else:
            spec = P(axes)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return Lanes(**{
        f: jax.tree_util.tree_map(lambda l: put(f, l), getattr(lanes, f))
        for f in Lanes._fields})
