"""Per-round telemetry collector shared by both drivers (DESIGN.md §8).

One :class:`RoundCollector` instance rides along a ``Solver.solve`` run
or a ``SolverService``; the driver calls it at round boundaries:

  start(lanes)                  once, after init/restore (baseline)
  before_round(lanes, dirty)    after host-side lane surgery (admission,
                                pending-pool installs) — refreshes the
                                baseline when ``dirty`` so steal counts
                                measure the jitted round ONLY
  after_round(round, lanes, …)  after the jitted round — computes deltas,
                                updates the metrics registry, appends
                                trace records; returns the per-instance
                                node delta (the service reuses it for
                                node-budget accounting)
  lifecycle(kind, …)            admit/retire/expire/cancel/reject hooks
  finish(rounds, best)          writes the trace ``summary`` record

Collection cost model: everything is derived from the per-lane counters
the engine already maintains on device (``nodes``/``t_s``/``t_r``/
``donated``/``t_c``, the ``active``/``inst``/``base`` control arrays and
the incumbent table).  Those are O(W) int32 arrays pulled to host once
per round — after the round's own open-work sync, so no NEW device syncs
land on the hot path, and nothing here feeds back into device state: the
search tree is bit-identical with telemetry on or off.

Shipped-subtree depth: a lane whose ``t_s`` rose this round received a
stolen task, and ``base`` is exactly the installed task's depth — so the
ship-size histogram (subtree depth ≈ log-size proxy) costs nothing
extra, and so does the replay's trip count: the batched replay runs as
deep as the deepest task received.  Kernel dispatches are
``ceil(steps / fused_steps)`` per round — the expand loop launches one
fused group per iteration (DESIGN.md §5.5).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.api import INF_VALUE
from repro.obs.registry import MetricsRegistry, MetricsSnapshot
from repro.obs.trace import TRACE_SCHEMA_VERSION, TraceWriter

__all__ = ["RoundCollector"]

# The incumbent watermark starts at the engine's "no solution" sentinel so
# a slot still at INF_VALUE never registers as an improvement.
_INF = int(INF_VALUE)

#: Subtree-depth buckets for the shipped-task histogram.
_SHIP_BUCKETS = (1, 2, 4, 8, 16, 32, 64)
#: Round-count buckets for scheduler wait/run histograms.
_ROUND_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


class RoundCollector:
    """Host-side per-round metrics + trace collection for one run."""

    def __init__(self, *, mode: str, lanes: int, slots: int,
                 steps_per_round: int, fused_steps: int = 1,
                 backend: str = "jnp", devices: int = 1,
                 registry: Optional[MetricsRegistry] = None,
                 trace: Optional[TraceWriter] = None):
        if mode not in ("solve", "service"):
            raise ValueError(f"mode must be 'solve' or 'service', got {mode!r}")
        self.mode = mode
        self.num_lanes = int(lanes)
        self.slots = int(slots)
        self.devices = max(1, int(devices))   # lane pool partitions (mesh)
        self.fused_steps = max(1, int(fused_steps))
        self.registry = registry if registry is not None else MetricsRegistry()
        self.trace = trace

        r = self.registry
        self.c_rounds = r.counter("engine_rounds", "service/solve rounds run")
        self.c_nodes = r.counter("engine_nodes", "search nodes expanded")
        self.c_steps = r.counter("engine_steps", "engine steps executed")
        self.c_dispatches = r.counter(
            "engine_dispatches",
            "fused step-group launches (ceil(steps/fused_steps) per round)")
        self.c_steal_req = r.counter("steal_requests",
                                     "task requests made (paper T_R)")
        self.c_steal_recv = r.counter(
            "steal_received",
            "tasks received via stealing (paper T_S), by scope label")
        self.c_donated = r.counter("steal_donated", "tasks donated")
        self.c_incumbent = r.counter("incumbent_improvements",
                                     "per-instance incumbent improvements")
        self.g_util = r.gauge("lane_utilization",
                              "active-lane fraction at the last round end")
        self.g_open = r.gauge("open_work", "total open work at last round end")
        self.h_ship = r.histogram("steal_ship_depth",
                                  "depth of shipped subtree roots",
                                  buckets=_SHIP_BUCKETS)
        self.c_replay = r.counter(
            "steal_replay_steps",
            "CONVERTINDEX replay trips: the deepest shipped task a round")
        self.g_dev_nodes = r.gauge(
            "device_nodes", "nodes expanded last round, per device shard")
        self.g_dev_active = r.gauge(
            "device_active_lanes", "active lanes at round end, per device")
        if mode == "service":
            self.g_queue = r.gauge("service_queue_depth",
                                   "queued (unadmitted) requests")
            self.h_wait = r.histogram("service_wait_rounds",
                                      "rounds queued before admission",
                                      buckets=_ROUND_BUCKETS)
            self.h_run = r.histogram("service_run_rounds",
                                     "rounds from admission to resolution",
                                     buckets=_ROUND_BUCKETS)

        self._base: Optional[Dict[str, np.ndarray]] = None
        self._best_seen = np.full((self.slots,), _INF, np.int64)
        self._inst_nodes = np.zeros((self.slots,), np.int64)
        self._lane = {k: np.zeros((self.num_lanes,), np.int64)
                      for k in ("nodes", "recv", "req", "donated", "cross")}
        self._steps = 0
        self._dispatches = 0
        self._rounds_seen = 0
        if trace is not None:
            trace.write("meta", schema=TRACE_SCHEMA_VERSION, mode=mode,
                        lanes=self.num_lanes, slots=self.slots,
                        steps_per_round=int(steps_per_round),
                        fused_steps=self.fused_steps, backend=backend,
                        devices=self.devices)

    # -- round boundaries ---------------------------------------------------

    def _read(self, lanes) -> Dict[str, np.ndarray]:
        return {
            "nodes": np.asarray(lanes.nodes, np.int64),
            "t_s": np.asarray(lanes.t_s, np.int64),
            "t_r": np.asarray(lanes.t_r, np.int64),
            "donated": np.asarray(lanes.donated, np.int64),
            "t_c": np.asarray(lanes.t_c, np.int64),
            "steps": np.asarray(lanes.steps, np.int64).reshape(()),
        }

    def start(self, lanes) -> None:
        """Capture the delta baseline (call after init or restore, so a
        restored checkpoint's carried totals never count as this run's)."""
        self._base = self._read(lanes)

    def before_round(self, lanes, dirty: bool) -> None:
        """Refresh the baseline iff host-side surgery touched the lanes
        since ``after_round`` (admissions and pool installs bump ``t_s``;
        without the refresh they would masquerade as steals)."""
        if dirty or self._base is None:
            self._base = self._read(lanes)

    def after_round(self, round_no: int, lanes, open_total: int, *,
                    queue_depth: int = 0,
                    slot_rids: Optional[Sequence[int]] = None) -> np.ndarray:
        """Ingest one finished jitted round; returns int64[K] node deltas."""
        cur = self._read(lanes)
        base = self._base if self._base is not None else {
            k: np.zeros_like(v) for k, v in cur.items()}
        d_nodes = cur["nodes"] - base["nodes"]
        d_recv = cur["t_s"] - base["t_s"]
        d_req = cur["t_r"] - base["t_r"]
        d_don = cur["donated"] - base["donated"]
        d_cross = cur["t_c"] - base["t_c"]
        d_steps = int(cur["steps"] - base["steps"])
        self._base = cur

        inst = np.asarray(lanes.inst)
        active = np.asarray(lanes.active)
        lane_base = np.asarray(lanes.base)
        best = np.asarray(lanes.best)

        inst_delta = np.zeros((self.slots,), np.int64)
        bound = inst >= 0
        np.add.at(inst_delta, inst[bound], d_nodes[bound])
        self._inst_nodes += inst_delta
        for key, d in (("nodes", d_nodes), ("recv", d_recv), ("req", d_req),
                       ("donated", d_don), ("cross", d_cross)):
            self._lane[key] += d
        dispatches = -(-d_steps // self.fused_steps) if d_steps > 0 else 0
        self._steps += d_steps
        self._dispatches += dispatches
        self._rounds_seen += 1
        ship_depths = [int(d) for d in lane_base[d_recv > 0]]

        self.c_rounds.inc()
        self.c_nodes.inc(int(d_nodes.sum()))
        self.c_steps.inc(d_steps)
        self.c_dispatches.inc(dispatches)
        self.c_steal_req.inc(int(d_req.sum()))
        n_cross = int(d_cross.sum())
        self.c_steal_recv.inc(int(d_recv.sum()) - n_cross, scope="intra")
        self.c_steal_recv.inc(n_cross, scope="cross")
        self.c_donated.inc(int(d_don.sum()))
        self.g_util.set(float(active.mean()) if active.size else 0.0)
        self.g_open.set(int(open_total))
        for depth in ship_depths:
            self.h_ship.observe(depth)
        self.c_replay.inc(max(ship_depths, default=0))
        if self.mode == "service":
            self.g_queue.set(int(queue_depth))

        # Per-device lane metrics: the pool shards its leading dim evenly
        # over the mesh, so device d owns lanes [d*W/D, (d+1)*W/D).
        dev_nodes = dev_active = None
        if self.devices > 1 and self.num_lanes % self.devices == 0:
            dev_nodes = d_nodes.reshape(self.devices, -1).sum(axis=1)
            dev_active = active.reshape(self.devices, -1).sum(axis=1)
            for d in range(self.devices):
                self.g_dev_nodes.set(int(dev_nodes[d]), device=d)
                self.g_dev_active.set(int(dev_active[d]), device=d)

        improved = []
        for slot in range(self.slots):
            b = int(best[slot])
            if b < self._best_seen[slot]:
                self._best_seen[slot] = b
                rid = None
                if slot_rids is not None and int(slot_rids[slot]) >= 0:
                    rid = int(slot_rids[slot])
                self.c_incumbent.inc()
                improved.append((slot, b, rid))

        if self.trace is not None:
            self.trace.write(
                "round", round=int(round_no), open=int(open_total),
                active=int(active.sum()), nodes=int(d_nodes.sum()),
                steal_req=int(d_req.sum()), steal_recv=int(d_recv.sum()),
                steal_recv_cross=n_cross, donated=int(d_don.sum()),
                steps=d_steps, dispatches=dispatches,
                inst_nodes=[int(x) for x in inst_delta],
                ship_depths=ship_depths, best=[int(b) for b in best],
                queue_depth=int(queue_depth),
                dev_nodes=(None if dev_nodes is None
                           else [int(x) for x in dev_nodes]),
                dev_active=(None if dev_active is None
                            else [int(x) for x in dev_active]))
            for slot, b, rid in improved:
                self.trace.write("incumbent", round=int(round_no), inst=slot,
                                 best=b, rid=rid)
        return inst_delta

    # -- elastic events -----------------------------------------------------

    def resize(self, num_lanes: int, *, devices: int,
               round_no: int) -> None:
        """Re-shape the per-lane accounting after an elastic pool resize.

        Mirrors the engine's carried-counter convention (checkpoint
        restore / ``repartition`` sum each counter onto lane 0): the
        accumulated per-lane totals collapse onto lane 0 of the new
        layout, so the summary ledger — sum(lane_nodes) == nodes ==
        sum(inst_nodes) — stays exact across any number of resizes.  The
        delta baseline is dropped; the driver re-baselines via
        ``before_round(dirty=True)`` on the rebuilt lanes.
        """
        self.num_lanes = int(num_lanes)
        self.devices = max(1, int(devices))
        for key, old in self._lane.items():
            carried = np.zeros((self.num_lanes,), np.int64)
            carried[0] = old.sum()
            self._lane[key] = carried
        self._base = None
        if self.trace is not None:
            self.trace.write("resize", round=int(round_no),
                             lanes=self.num_lanes, devices=self.devices)

    # -- request lifecycle (service) ----------------------------------------

    def lifecycle(self, kind: str, *, round_no: int, rid: int,
                  slot: Optional[int] = None, best: Optional[int] = None,
                  waited: Optional[int] = None, ran: Optional[int] = None,
                  reason: Optional[str] = None) -> None:
        """One request transition: histogram wait/run rounds and append the
        trace record.  An admitted slot's incumbent watermark resets so the
        next tenant's improvements are reported from scratch."""
        if kind == "admit":
            if slot is not None:
                self._best_seen[slot] = _INF
            if waited is not None and self.mode == "service":
                self.h_wait.observe(int(waited))
        elif kind in ("retire", "expire", "cancel"):
            if ran is not None and self.mode == "service":
                self.h_run.observe(int(ran))
        if self.trace is not None:
            self.trace.write(kind, round=int(round_no), rid=int(rid),
                             slot=slot, best=best, waited=waited, ran=ran,
                             reason=reason)

    # -- wrap-up ------------------------------------------------------------

    def finish(self, *, rounds: int,
               best: Optional[List[int]] = None) -> None:
        """Append the trace ``summary`` (per-lane/-instance totals this run).
        Callable repeatedly — a service summarizes after every drain and
        readers take the last summary."""
        if self.trace is not None:
            self.trace.write(
                "summary", round=int(rounds), rounds=self._rounds_seen,
                nodes=int(self._lane["nodes"].sum()),
                best=best,
                lane_nodes=[int(x) for x in self._lane["nodes"]],
                lane_recv=[int(x) for x in self._lane["recv"]],
                lane_req=[int(x) for x in self._lane["req"]],
                lane_donated=[int(x) for x in self._lane["donated"]],
                lane_cross=[int(x) for x in self._lane["cross"]],
                inst_nodes=[int(x) for x in self._inst_nodes],
                steps=self._steps, dispatches=self._dispatches)

    def close(self) -> None:
        if self.trace is not None:
            self.trace.close()

    def snapshot(self) -> MetricsSnapshot:
        return self.registry.snapshot()
