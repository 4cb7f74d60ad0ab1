"""Checkpoint / restart for the solver (paper §VII, made first-class).

The paper observes that under indexed search trees, checkpointing is
"reasonably straightforward ... by forcing every core to write its
current_idx to some file".  We implement exactly that, plus the elastic
half the paper only gestures at (join-leave):

* ``save`` — persist every lane's ``(idx, depth, base, inst, active)`` plus
  the per-instance incumbent table to a single ``.npz``.  The *entire*
  solver state is O(W · D_MAX) int8 — the compact-encoding payoff again;
  stacks are NOT saved, they are reconstructed by CONVERTINDEX replay on
  restore.  ``extra`` lets callers (the solver service) ride metadata
  arrays in the same atomic file; non-array host metadata (the service's
  queued-request heap and ticket states) rides as JSON bytes via
  ``pack_json``/``unpack_json``.

* ``restore`` — rebuild ``Lanes`` for an arbitrary new lane count W'
  (elastic shrink/grow).  The first W' active tasks are installed directly;
  any surplus is returned as a host-side *pending pool* the driver feeds to
  idle lanes at round boundaries (``repro.core.distributed.solve`` and
  ``repro.service.driver`` consume it).  Nothing is ever lost or explored
  twice: an installed lane resumes from its exact ``current_idx``
  (delegation marks intact), and pool entries are unmodified lane images —
  each tagged with its instance, so multi-tenant restores keep tenant
  isolation.
"""

from __future__ import annotations

import io
import json
import os
import tempfile
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import UNVISITED, INF_VALUE, BinaryProblem
from repro.core.engine import Lanes, init_lanes, replay_lanes

_EXTRA_PREFIX = "extra_"


def save(path: str, lanes: Lanes,
         extra: Optional[Dict[str, np.ndarray]] = None) -> None:
    """Atomically persist lane control state + incumbents (not the stacks).

    ``extra`` arrays are stored under an ``extra_`` prefix and returned by
    :func:`read_extra` — the service driver uses this for its slot tables.
    """
    payload_leaves, _ = jax.tree_util.tree_flatten(lanes.best_payload)
    arrays = {
        "idx": np.asarray(lanes.idx, dtype=np.int8),
        "depth": np.asarray(lanes.depth, dtype=np.int32),
        "base": np.asarray(lanes.base, dtype=np.int32),
        "inst": np.asarray(lanes.inst, dtype=np.int32),
        "active": np.asarray(lanes.active),
        "best": np.asarray(lanes.best, dtype=np.int32),
        "nodes": np.asarray(lanes.nodes, dtype=np.int32),
        "t_s": np.asarray(lanes.t_s, dtype=np.int32),
        "t_r": np.asarray(lanes.t_r, dtype=np.int32),
        "donated": np.asarray(lanes.donated, dtype=np.int32),
        "t_c": np.asarray(lanes.t_c, dtype=np.int32),
        "steps": np.asarray(lanes.steps, dtype=np.int32),
    }
    for i, leaf in enumerate(payload_leaves):
        arrays[f"payload_{i}"] = np.asarray(leaf)
    for key, val in (extra or {}).items():
        arrays[_EXTRA_PREFIX + key] = np.asarray(val)
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    data = buf.getvalue()
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".ckpt.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)          # atomic on POSIX: no torn checkpoints
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def read_extra(path: str) -> Dict[str, np.ndarray]:
    """Read back the ``extra`` arrays stored by :func:`save`."""
    out = {}
    with np.load(path) as z:
        for key in z.files:
            if key.startswith(_EXTRA_PREFIX):
                out[key[len(_EXTRA_PREFIX):]] = z[key]
    return out


def pack_json(obj: Any) -> np.ndarray:
    """Encode a JSON-serializable object as a uint8 array.

    Checkpoints are single ``.npz`` files written without pickling;
    structured host metadata that is not naturally an array (the service's
    queued-request heap and ticket states) rides as UTF-8 JSON bytes in an
    ordinary ``extra`` array instead.  Inverse: :func:`unpack_json`.
    """
    return np.frombuffer(json.dumps(obj).encode("utf-8"), np.uint8).copy()


def unpack_json(arr: np.ndarray) -> Any:
    """Decode an array written by :func:`pack_json`."""
    return json.loads(np.asarray(arr, np.uint8).tobytes().decode("utf-8"))


class PendingTask:
    """A not-yet-installed lane image (elastic surplus), instance-tagged."""

    __slots__ = ("idx", "depth", "base", "inst")

    def __init__(self, idx: np.ndarray, depth: int, base: int, inst: int = 0):
        self.idx, self.depth, self.base, self.inst = idx, depth, base, inst


def restore(path: str, problem: BinaryProblem, num_lanes: int
            ) -> Tuple[Lanes, List[PendingTask]]:
    """Rebuild Lanes for ``num_lanes`` (elastic) + surplus pending pool."""
    with np.load(path) as z:
        idx = z["idx"]
        depth, base, active = z["depth"], z["base"], z["active"]
        inst = (z["inst"] if "inst" in z
                else np.zeros(idx.shape[0], np.int32))
        best = np.atleast_1d(np.asarray(z["best"], np.int32))
        payload_leaves = []
        i = 0
        while f"payload_{i}" in z:
            payload_leaves.append(z[f"payload_{i}"])
            i += 1
        # t_c is absent from pre-telemetry checkpoints: carry what exists.
        stats = {k: z[k] for k in ("nodes", "t_s", "t_r", "donated", "t_c")
                 if k in z}
        steps = int(z["steps"])

    lanes = init_lanes(problem, num_lanes, seed_root=False)
    if best.shape[0] != problem.num_instances:
        raise ValueError(
            f"checkpoint has {best.shape[0]} instance slots, problem has "
            f"{problem.num_instances}; elastic restore varies LANES, not K")
    proto = jax.tree_util.tree_structure(lanes.best_payload)
    payload = (jax.tree_util.tree_unflatten(
        proto, [jnp.asarray(l) for l in payload_leaves])
        if payload_leaves else lanes.best_payload)

    live = [k for k in range(idx.shape[0]) if active[k]]
    installed, pending = live[:num_lanes], live[num_lanes:]

    il = lanes.idx.shape[1]
    new_idx = np.full((num_lanes, il), int(UNVISITED), np.int8)
    new_depth = np.zeros((num_lanes,), np.int32)
    new_base = np.zeros((num_lanes,), np.int32)
    new_inst = np.zeros((num_lanes,), np.int32)
    new_active = np.zeros((num_lanes,), bool)
    for j, k in enumerate(installed):
        w = min(il, idx.shape[1])
        new_idx[j, :w] = idx[k, :w]
        new_depth[j], new_base[j] = depth[k], base[k]
        new_inst[j], new_active[j] = inst[k], True

    lanes = lanes._replace(
        idx=jnp.asarray(new_idx), depth=jnp.asarray(new_depth),
        base=jnp.asarray(new_base), inst=jnp.asarray(new_inst),
        active=jnp.asarray(new_active),
        best=jnp.asarray(best), best_payload=payload,
        steps=jnp.int32(steps))
    lanes = rebuild_stacks(problem, lanes)

    # Aggregate stats are carried on lane 0 so totals survive re-sharding.
    carry = {k: int(v.sum()) for k, v in stats.items()}
    lanes = lanes._replace(
        nodes=lanes.nodes.at[0].add(carry["nodes"]),
        t_s=lanes.t_s.at[0].add(carry["t_s"]),
        t_r=lanes.t_r.at[0].add(carry["t_r"]),
        donated=lanes.donated.at[0].add(carry["donated"]),
        t_c=lanes.t_c.at[0].add(carry.get("t_c", 0)))

    pool = [PendingTask(idx[k].copy(), int(depth[k]), int(base[k]),
                        int(inst[k]))
            for k in pending]
    return lanes, pool


def repartition(problem: BinaryProblem, lanes: Lanes, num_lanes: int
                ) -> Tuple[Lanes, List[PendingTask]]:
    """In-memory elastic W → W' re-layout (the checkpoint/restore cycle
    without the file): the first W' live tasks are installed onto fresh
    lanes, surplus becomes an instance-tagged pending pool, and aggregate
    stats are carried on lane 0 — exactly :func:`restore`'s contract.  The
    service's autoscaling hook uses this to add/remove devices mid-run.

    ``lanes`` must be host-addressable (gather before calling under a
    mesh); unbound idle lanes (inst == NO_INSTANCE) are dropped — idle
    lanes of the new pool start unbound.
    """
    idx = np.asarray(lanes.idx)
    depth = np.asarray(lanes.depth)
    base = np.asarray(lanes.base)
    inst = np.asarray(lanes.inst)
    active = np.asarray(lanes.active)
    stats = {k: int(np.asarray(getattr(lanes, k)).sum())
             for k in ("nodes", "t_s", "t_r", "donated", "t_c")}

    new = init_lanes(problem, num_lanes, seed_root=False)
    new = new._replace(
        inst=jnp.full((num_lanes,), -1, jnp.int32),
        best=jnp.asarray(np.asarray(lanes.best)),
        best_payload=jax.tree_util.tree_map(
            lambda p: jnp.asarray(np.asarray(p)), lanes.best_payload),
        steps=jnp.asarray(np.asarray(lanes.steps)))

    live = [k for k in range(idx.shape[0]) if active[k]]
    installed, pending = live[:num_lanes], live[num_lanes:]

    il = new.idx.shape[1]
    new_idx = np.full((num_lanes, il), int(UNVISITED), np.int8)
    new_depth = np.zeros((num_lanes,), np.int32)
    new_base = np.zeros((num_lanes,), np.int32)
    new_inst = np.full((num_lanes,), -1, np.int32)
    new_active = np.zeros((num_lanes,), bool)
    for j, k in enumerate(installed):
        w = min(il, idx.shape[1])
        new_idx[j, :w] = idx[k, :w]
        new_depth[j], new_base[j] = depth[k], base[k]
        new_inst[j], new_active[j] = inst[k], True
    new = new._replace(
        idx=jnp.asarray(new_idx), depth=jnp.asarray(new_depth),
        base=jnp.asarray(new_base), inst=jnp.asarray(new_inst),
        active=jnp.asarray(new_active))
    new = rebuild_stacks(problem, new)
    new = new._replace(
        nodes=new.nodes.at[0].add(stats["nodes"]),
        t_s=new.t_s.at[0].add(stats["t_s"]),
        t_r=new.t_r.at[0].add(stats["t_r"]),
        donated=new.donated.at[0].add(stats["donated"]),
        t_c=new.t_c.at[0].add(stats["t_c"]))
    pool = [PendingTask(idx[k].copy(), int(depth[k]), int(base[k]),
                        int(inst[k]))
            for k in pending]
    return new, pool


def rebuild_stacks(problem: BinaryProblem, lanes: Lanes) -> Lanes:
    """CONVERTINDEX for every active lane: replay path bits to its node.

    The path to a lane's *current node* is ``idx[0..depth-1]`` with
    delegation marks flattened to the branch actually taken (DELEGATED means
    the donor went left).  Replay starts from the root of the lane's OWN
    instance, in one batched replay over the lane block
    (``engine.replay_lanes``) as deep as the deepest active lane: at most
    D_MAX applies over all lanes, paid once per restore or admission.
    """
    k = lanes.best.shape[0]
    safe_inst = jnp.clip(lanes.inst, 0, k - 1)
    stack = replay_lanes(problem, lanes.idx, lanes.depth, safe_inst,
                         lanes.active, lanes.stack)
    return lanes._replace(stack=stack)


def install_pending(problem: BinaryProblem, lanes: Lanes,
                    pool: List[PendingTask]) -> Tuple[Lanes, List[PendingTask]]:
    """Feed pending pool entries to idle lanes (driver, round boundaries)."""
    if not pool:
        return lanes, pool
    active = np.asarray(lanes.active)
    idle = [i for i in range(active.shape[0]) if not active[i]]
    n = min(len(idle), len(pool))
    if n == 0:
        return lanes, pool
    il = lanes.idx.shape[1]
    idxs = np.asarray(lanes.idx).copy()
    depth = np.asarray(lanes.depth).copy()
    base = np.asarray(lanes.base).copy()
    inst = np.asarray(lanes.inst).copy()
    act = active.copy()
    t_s = np.asarray(lanes.t_s).copy()
    for lane, task in zip(idle[:n], pool[:n]):
        w = min(il, task.idx.shape[0])
        idxs[lane, :w] = task.idx[:w]
        depth[lane], base[lane], act[lane] = task.depth, task.base, True
        inst[lane] = task.inst
        t_s[lane] += 1
    lanes = lanes._replace(
        idx=jnp.asarray(idxs), depth=jnp.asarray(depth),
        base=jnp.asarray(base), inst=jnp.asarray(inst),
        active=jnp.asarray(act), t_s=jnp.asarray(t_s))
    return rebuild_stacks(problem, lanes), pool[n:]
