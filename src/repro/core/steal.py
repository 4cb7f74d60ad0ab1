"""Heaviest-task work stealing between lanes on one device (paper §IV-A/B).

Every steal round, idle lanes (*thieves*) are matched with active lanes that
have an open right-branch (*donors*).  Donor priority is the paper's implicit
weight: the lane whose shallowest open slot is closest to the root donates
first (w = 1/(d+1)).  Extraction is GETHEAVIESTTASKINDEX (mark DELEGATED,
ship the prefix) and installation is FIXINDEX + CONVERTINDEX (replay).

The donor→thief pairing is a deterministic ranked matching — the
bulk-synchronous closed form of the paper's virtual-topology heuristic
("request from the core expected to hold the heaviest task"): sorting donors
by weight and pairing them with thieves in rank order is exactly what the
GETPARENT tree converges to, computed in one argsort instead of message
probing.

Instance scoping (the solver-service invariant).  With K > 1 instances
multiplexed over the lane pool, the matching is keyed by ``(inst, slot,
lane)``: a thief is paired only with a donor of the SAME instance, so one
tenant's starvation never leaks work (or search-tree nodes) from another.
Lanes with ``inst == NO_INSTANCE`` neither steal nor donate.  With K = 1
every lane has inst 0 and the matching degenerates to the original global
ranked matching.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.api import UNVISITED, BinaryProblem
from repro.core.engine import Lanes, replay_lanes
from repro.core.indexing import extract_task, heaviest_open_slot


def donor_slots(lanes: Lanes) -> jnp.ndarray:
    """Per-lane shallowest open slot (IDX_LEN = no donatable work)."""
    return jax.vmap(heaviest_open_slot)(lanes.idx, lanes.base, lanes.depth)


def donor_mask(lanes: Lanes, slots: jnp.ndarray) -> jnp.ndarray:
    """Lanes that could donate: active, bound to an instance, open slot."""
    il = lanes.idx.shape[1]
    return lanes.active & (lanes.inst >= 0) & (slots < il)


def thief_mask(lanes: Lanes) -> jnp.ndarray:
    """Lanes that may receive work: idle but bound to an instance."""
    return (~lanes.active) & (lanes.inst >= 0)


def _rank_within_instance(member: jnp.ndarray, key: jnp.ndarray,
                          inst: jnp.ndarray) -> jnp.ndarray:
    """Rank of each member lane among same-instance members, by ``key``.

    O(W^2) boolean reduction — W is a per-device lane count (tens to a few
    hundred), so the [W, W] mask is tiny next to the lane stacks.
    """
    same = inst[:, None] == inst[None, :]
    better = member[None, :] & same & (key[None, :] < key[:, None])
    return jnp.sum(better.astype(jnp.int32), axis=1)


def match_thieves_to_donors(lanes: Lanes, slots: jnp.ndarray
                            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Instance-scoped ranked matching.

    Returns (src, matched, is_donor): per-lane donor lane id each thief
    draws from (arbitrary where not matched), the per-lane "this thief got
    a task" mask, and the per-lane "this donor was drained" mask.  The
    matching pairs the r-th thief of instance i (lane-id order) with the
    r-th donor of instance i (heaviest-first: slot depth, lane-id
    tiebreak) — for K = 1 this is exactly the original global matching.
    """
    w = lanes.idx.shape[0]
    lane_ids = jnp.arange(w, dtype=jnp.int32)
    donors = donor_mask(lanes, slots)
    thieves = thief_mask(lanes)
    dkey = slots * w + lane_ids                    # weight-major, lane tiebreak
    drank = _rank_within_instance(donors, dkey, lanes.inst)
    trank = _rank_within_instance(thieves, lane_ids, lanes.inst)
    same = lanes.inst[:, None] == lanes.inst[None, :]
    pair = (thieves[:, None] & donors[None, :] & same
            & (trank[:, None] == drank[None, :]))
    src = jnp.argmax(pair, axis=1).astype(jnp.int32)
    matched = jnp.any(pair, axis=1)
    is_donor = jnp.any(pair, axis=0)
    return src, matched, is_donor


def extract_tasks(lanes: Lanes, quota: jnp.ndarray, max_tasks: int
                  ) -> Tuple[Lanes, jnp.ndarray, jnp.ndarray, jnp.ndarray,
                             jnp.ndarray, jnp.ndarray]:
    """Extract the per-instance ``quota[i]`` heaviest tasks of each instance.

    ``quota`` is int32[K].  Returns (lanes', bits[max_tasks, IDX_LEN],
    task_depth[max_tasks], task_inst[max_tasks], task_rank[max_tasks],
    valid[max_tasks]).  Tasks are extracted from distinct lanes in
    (instance, weight) order; ``task_rank`` is the task's rank WITHIN its
    instance on this device (the cross-device claim key).  Donor lanes get
    their slot marked DELEGATED and ``donated`` incremented.
    """
    w, il = lanes.idx.shape
    k = quota.shape[0]
    lane_ids = jnp.arange(w, dtype=jnp.int32)
    slots = donor_slots(lanes)
    can = donor_mask(lanes, slots)
    dkey = slots * w + lane_ids
    drank = _rank_within_instance(can, dkey, lanes.inst)
    safe_inst = jnp.clip(lanes.inst, 0, k - 1)
    is_donor = can & (drank < quota[safe_inst])

    new_idx_all, bits_all = jax.vmap(extract_task)(lanes.idx, slots)
    new_idx = jnp.where(is_donor[:, None], new_idx_all, lanes.idx)
    lanes = lanes._replace(
        idx=new_idx, donated=lanes.donated + is_donor.astype(jnp.int32))

    # Ship rows in (instance, weight) order: instance-major key sort.
    key = jnp.where(is_donor, safe_inst * (il * w) + dkey,
                    jnp.int32(k * il * w + w))
    order = jnp.argsort(key)
    sel = order[:max_tasks]
    valid = is_donor[sel]
    bits = jnp.where(valid[:, None], bits_all[sel], UNVISITED)
    tdepth = jnp.where(valid, slots[sel] + 1, 0)
    tinst = jnp.where(valid, safe_inst[sel], 0)
    trank = jnp.where(valid, drank[sel], 0)
    return lanes, bits.astype(jnp.int8), tdepth, tinst, trank, valid


def claim_tasks(thieves: jnp.ndarray, inst: jnp.ndarray,
                my_grank: jnp.ndarray, w_inst: jnp.ndarray,
                w_grank: jnp.ndarray, w_valid: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-instance rank-arithmetic claim (cross-device step 4).

    ``thieves``/``inst``/``my_grank`` describe the local lanes (bool[W],
    int32[W], int32[W]); ``w_inst``/``w_grank``/``w_valid`` describe the
    gathered world task rows ([D*S]).  Returns ``(src, claim)``: the world
    row each lane claims (arbitrary where unclaimed) and the claim mask.

    Invariant (the PR-1 bug class, property-tested in
    ``tests/test_steal_quota.py``): when ``(inst, grank)`` is unique among
    valid rows and among thieves — which the quota construction guarantees
    — claims form a bijection between matching rows and thieves, and a
    thief only ever claims a row of its own instance.
    """
    pair = (thieves[:, None] & w_valid[None, :]
            & (w_inst[None, :] == inst[:, None])
            & (w_grank[None, :] == my_grank[:, None]))       # [W, D*S]
    src = jnp.argmax(pair, axis=1)
    claim = jnp.any(pair, axis=1)
    return src, claim


def install_tasks(problem: BinaryProblem, lanes: Lanes, bits: jnp.ndarray,
                  tdepth: jnp.ndarray, tinst: jnp.ndarray,
                  valid: jnp.ndarray, cross: bool = False) -> Lanes:
    """Install per-LANE task rows (FIXINDEX was applied at extraction).

    Row ``i`` goes to lane ``i`` — callers route tasks to specific thief
    lanes (``valid`` gates installation; it must only be set on idle
    lanes).  Receiving lanes rebuild their state stacks from the root of
    the task's instance in one batched CONVERTINDEX replay
    (``engine.replay_lanes``, as deep as the deepest received task), then
    resume as owners of the stolen subtree (base = task depth).  ``cross``
    (a static flag, True from ``cross_device_steal``) additionally bumps
    the receiver's ``t_c`` counter so telemetry can split steal traffic
    into intra- vs cross-device scope.
    """
    my_valid = valid & ~lanes.active
    stack = replay_lanes(problem, bits, tdepth, tinst, my_valid, lanes.stack)

    idx = jnp.where(my_valid[:, None], bits, lanes.idx)
    recv = my_valid.astype(jnp.int32)
    return lanes._replace(
        idx=idx,
        depth=jnp.where(my_valid, tdepth, lanes.depth),
        base=jnp.where(my_valid, tdepth, lanes.base),
        inst=jnp.where(my_valid, tinst, lanes.inst),
        active=lanes.active | my_valid,
        stack=stack,
        t_s=lanes.t_s + recv,
        t_c=lanes.t_c + recv if cross else lanes.t_c,
    )


def balance_device(problem: BinaryProblem, lanes: Lanes) -> Lanes:
    """One intra-device steal round: same-instance thief/donor matching."""
    slots = donor_slots(lanes)
    thieves = thief_mask(lanes)
    # Every bound idle lane "requests" this round (paper's T_R accounting).
    lanes = lanes._replace(t_r=lanes.t_r + thieves.astype(jnp.int32))
    src, matched, is_donor = match_thieves_to_donors(lanes, slots)

    new_idx_all, bits_all = jax.vmap(extract_task)(lanes.idx, slots)
    lanes = lanes._replace(
        idx=jnp.where(is_donor[:, None], new_idx_all, lanes.idx),
        donated=lanes.donated + is_donor.astype(jnp.int32))

    bits = jnp.where(matched[:, None], bits_all[src], UNVISITED).astype(
        jnp.int8)
    tdepth = jnp.where(matched, slots[src] + 1, 0)
    tinst = jnp.where(matched, lanes.inst[src], 0)
    return install_tasks(problem, lanes, bits, tdepth, tinst, matched)
