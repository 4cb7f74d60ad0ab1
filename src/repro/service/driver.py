"""Continuous-batching solver service over one lane pool.

The continuous-batching serving pattern (fixed slot pool, lockstep ticks,
admission/retirement at tick boundaries) applied to backtracking:

  * the *pool* is W engine lanes advancing in lockstep under one jitted
    round (expand → instance-scoped steal → per-instance termination);
  * a *slot* is one of K stacked-instance table entries
    (``batch_problem.StackedSpec``); a request occupies a slot from
    admission to retirement or eviction;
  * *admission* pops the next request from the pluggable scheduling policy
    (:mod:`repro.service.scheduler` — priority heap by default, NOT the
    submission order), resolves its family through the
    :mod:`repro.registry` and writes the padded instance into the stacked
    tables (they are jit ARGUMENTS, so no recompilation), resets the
    slot's incumbent and seeds the instance root onto one idle lane —
    every other lane the instance ever uses arrives via stealing, the
    same bootstrap the paper uses for its virtual topology;
  * *retirement* fires when the per-instance open-work counter reaches
    zero: the slot's optimum + payload are recorded, the ticket resolves
    DONE and the slot is free for the next queued request;
  * *eviction* fires on ``Ticket.cancel()``, a missed ``deadline_rounds``
    or an exhausted ``node_budget``: the slot's best-so-far is recorded
    as an anytime result, its lanes are deactivated and unbound within
    one round, and the ticket resolves CANCELLED / EXPIRED.

This module is the PURE ROUND-STEPPING ENGINE of the request lifecycle:
it owns lanes, tables and the admit → round → retire → evict mechanics.
Every "which request, when" decision (admission order, deadlines,
budgets) is delegated to the :class:`~repro.service.scheduler.Scheduler`
policy layer, so scheduling policies plug in without touching this file.

``submit()`` returns a :class:`~repro.service.ticket.Ticket` — a
future-like handle with ``status`` / ``result(timeout=)`` / ``cancel()``.
Lifecycle transitions stream through the typed
:class:`~repro.solver.ProgressEvent` stream (kinds ``admit``, ``retire``,
``incumbent`` — per-request anytime incumbents — ``reject``, ``cancel``,
``expire``).  The legacy surface (``run()``, int-rid tickets) remains as
DeprecationWarning shims, bitwise-identical on the default policy.

Tenant isolation: stealing (intra- and cross-device) never pairs lanes
across instances, and per-instance incumbents mean one tenant's bound
never prunes another's tree — a slot's result is bitwise identical to a
dedicated single-instance solve (asserted against the serial oracle by
``tests/test_service.py``).

Elastic operation: ``save``/``restore`` persist the whole service (lane
control state + slot tables + the queued-request heap + ticket states)
through ``repro.core.checkpoint``; restoring onto W' ≠ W lanes parks
surplus tasks in an instance-tagged pending pool that drains at round
boundaries, and a restored queue pops in exactly the saved order.

The shared evaluate's masked-popcount pass is backend-pluggable
(``backend="jnp" | "pallas"``, forwarded to ``StackedSpec.bind`` —
DESIGN.md §5.3); the search is bitwise-identical under either, so the
backend is an execution choice like the lane count, not checkpoint state.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import registry
from repro.core import checkpoint as ckpt
from repro.core.api import INF_VALUE, UNVISITED
from repro.core.distributed import (_gather_lanes, _shard_lanes,
                                    lane_partition_specs, make_round)
from repro.core.engine import NO_INSTANCE, init_lanes
from repro.problems.graphs import Graph, num_words
from repro.service.batch_problem import StackedSpec, StackedTables
from repro.service.scheduler import (AutoscalePolicy, Scheduler,
                                     SchedulingPolicy, QueueItem,
                                     make_policy)
from repro.service.ticket import (TERMINAL, AdmissionError, RequestResult,
                                  SolveRequest, Ticket, TicketStatus)

__all__ = [
    "AdmissionError",
    "RequestResult",
    "SolveRequest",
    "SolverService",
]


class _ResultMap(dict):
    """Results keyed by int rid; lookups normalize Tickets through
    ``int()`` so pre-ticket code (``results[svc.submit(r)]``) keeps
    working — via the Ticket.__int__ deprecation shim."""

    def __getitem__(self, key):
        return super().__getitem__(int(key))

    def __contains__(self, key):
        return super().__contains__(int(key))

    def get(self, key, default=None):
        return super().get(int(key), default)


class SolverService:
    """Fixed pool of W lanes continuously batched over streamed requests.

    Construct through :meth:`repro.solver.Solver.serve` (or
    :meth:`from_config`); direct ``SolverService(...)`` construction is the
    deprecated pre-facade surface and emits ``DeprecationWarning``.
    """

    def __init__(self, *, max_n: int, slots: int, num_lanes: int,
                 steps_per_round: int = 64, backend: str = "jnp",
                 scheduler: Union[str, SchedulingPolicy] = "priority",
                 fused_steps: int = 1):
        warnings.warn(
            "direct SolverService(...) construction is deprecated; use "
            "repro.solver.Solver(SolverConfig(...)).serve(max_n=..., "
            "slots=...)", DeprecationWarning, stacklevel=2)
        self._init(max_n=max_n, slots=slots, num_lanes=num_lanes,
                   steps_per_round=steps_per_round, backend=backend,
                   scheduler=scheduler, fused_steps=fused_steps)

    @classmethod
    def from_config(cls, config, *, max_n: int, slots: int,
                    on_event: Optional[Callable[[Any], None]] = None
                    ) -> "SolverService":
        """The facade constructor: lanes / steps_per_round / backend /
        scheduler / fused_steps / telemetry come from a
        :class:`repro.solver.SolverConfig`."""
        return cls._create(max_n=max_n, slots=slots,
                           num_lanes=config.lanes,
                           steps_per_round=config.steps_per_round,
                           backend=config.backend,
                           scheduler=config.scheduler,
                           fused_steps=getattr(config, "fused_steps", 1),
                           mesh=getattr(config, "mesh", None),
                           max_ship=getattr(config, "max_ship", 16),
                           autoscale=getattr(config, "autoscale", None),
                           trace_path=getattr(config, "trace_path", None),
                           metrics=getattr(config, "metrics", False),
                           on_event=on_event)

    @classmethod
    def _create(cls, **kwargs) -> "SolverService":
        svc = object.__new__(cls)
        svc._init(**kwargs)
        return svc

    def _init(self, *, max_n: int, slots: int, num_lanes: int,
              steps_per_round: int = 64, backend: str = "jnp",
              scheduler: Union[str, SchedulingPolicy] = "priority",
              fused_steps: int = 1, mesh: Optional[Mesh] = None,
              max_ship: int = 16,
              autoscale: Optional[AutoscalePolicy] = None,
              trace_path: Optional[str] = None, metrics: bool = False,
              on_event: Optional[Callable[[Any], None]] = None):
        self.spec = StackedSpec(n=max_n, k=slots)
        self.steps_per_round = steps_per_round
        self.backend = backend                # shared-evaluate kernel backend
        self.fused_steps = fused_steps        # S steps per expand iteration
        self.max_ship = max_ship              # cross-device ship cap / round
        self.on_event = on_event              # ProgressEvent stream (§6)
        self.autoscale = autoscale            # elasticity policy, or None
        self.tables = self.spec.empty_tables()           # host numpy
        self._tables_dev: Optional[StackedTables] = None

        # Mesh layout (DESIGN.md §9): ``num_lanes`` is the PER-DEVICE lane
        # count (SolverConfig.lanes semantics); the pool is partitioned
        # over the mesh and the round runs under shard_map with the
        # stacked tables and incumbent state replicated per device.
        self.mesh = mesh
        self.n_devices = (int(np.prod(mesh.devices.shape))
                          if mesh is not None else 1)
        self.lanes_per_device = num_lanes
        self.num_lanes = num_lanes * self.n_devices
        self._build_round_fns()

        proto = self.spec.bind(self._tables_host())
        self.lanes = self._place(init_lanes(proto, self.num_lanes,
                                            seed_root=False,
                                            bind_instance=False))

        policy = (scheduler if not isinstance(scheduler, str)
                  else make_policy(scheduler))
        self.sched = Scheduler(policy)
        self.slot_rid: List[int] = [-1] * slots          # -1 = free slot
        self.slot_admitted: List[int] = [0] * slots
        self._slot_best_seen: List[int] = [int(INF_VALUE)] * slots
        self.results: Dict[int, RequestResult] = _ResultMap()
        self.pool: List[ckpt.PendingTask] = []
        self.rounds = 0
        # True when the steady-state placement check last passed with at
        # most one live slot — _admit_and_place then skips its device
        # readback entirely until the next placement-changing event
        # (admission, retire/evict, resize, pool install) clears it.
        self._placement_clean = False

        # Telemetry (DESIGN.md §8): one RoundCollector rides the service,
        # fed host-side at round boundaries — no extra device syncs.
        self.metrics_enabled = bool(metrics)
        self._collector = None
        if metrics or trace_path is not None:
            from repro import obs
            self._collector = obs.RoundCollector(
                mode="service", lanes=self.num_lanes, slots=slots,
                steps_per_round=steps_per_round, fused_steps=fused_steps,
                backend=backend, devices=self.n_devices,
                trace=obs.TraceWriter(trace_path) if trace_path else None)
            self._collector.start(self.lanes)

    def _build_round_fns(self) -> None:
        """(Re)jit the round + stack-rebuild closures for the current mesh
        — called at construction and after every :meth:`resize`."""
        self._round, self._rebuild = make_service_round_fns(
            self.spec, self.backend, self.steps_per_round,
            fused_steps=self.fused_steps, mesh=self.mesh,
            max_ship=self.max_ship)

    def metrics(self):
        """``repro.obs.MetricsSnapshot`` of this service's registry, or
        None when telemetry is off (enable via
        ``SolverConfig(metrics=True)`` or ``trace_path=...``)."""
        return (self._collector.snapshot()
                if self._collector is not None else None)

    def finalize_trace(self) -> None:
        """Append a trace ``summary`` record (per-lane / per-instance
        totals so far).  Called automatically by :meth:`drain`; call it
        directly when stepping rounds by hand.  Idempotent — readers use
        the last summary."""
        if self._collector is not None:
            self._collector.finish(
                rounds=self.rounds,
                best=[int(b) for b in np.asarray(self.lanes.best)])

    # -- host/device plumbing ----------------------------------------------

    def _tables_host(self) -> StackedTables:
        """Uncommitted device copies, for eager host-side surgery."""
        return StackedTables(*(jnp.asarray(t) for t in self.tables))

    def _tables_jnp(self) -> StackedTables:
        if self._tables_dev is None:
            tables = self._tables_host()
            if self.mesh is not None:             # replicated per device
                tables = jax.device_put(tables, NamedSharding(self.mesh, P()))
            self._tables_dev = tables
        return self._tables_dev

    def _place(self, lanes):
        """Lay host-written lane state out over the mesh (lanes sharded,
        incumbents replicated); a no-op without a mesh."""
        return lanes if self.mesh is None else _shard_lanes(lanes, self.mesh)

    def _touch_tables(self) -> None:
        self._tables_dev = None

    # -- the ticketed front door -------------------------------------------

    @property
    def queue(self) -> Tuple[SolveRequest, ...]:
        """Queued (not yet admitted) requests, in pop order."""
        return tuple(item.request for item in self.sched.pending())

    @property
    def tickets(self) -> Dict[int, Ticket]:
        """Every ticket this service has issued, by rid."""
        return self.sched.tickets

    def submit(self, request: SolveRequest) -> Ticket:
        """Queue a request after full admission validation; returns its
        :class:`~repro.service.ticket.Ticket`.

        Any registered family with service packing is admissible — there is
        no per-family name table here; new families become servable the
        moment their ``@register_problem`` call supplies ``pack`` +
        ``family_id``.  Anything the service can never run raises
        :class:`AdmissionError` (never a deep packing failure), after a
        ``reject`` ProgressEvent so observers see refusals too.
        """
        reason = None
        try:
            spec = registry.get(request.family)
        except registry.UnknownProblemError as e:
            reason = str(e)
        else:
            n = spec.size(request.graph)
            if not spec.servable:
                reason = (f"problem family {request.family!r} is registered "
                          f"but not servable (no service packing in its "
                          f"@register_problem call)")
            elif n > self.spec.n:
                reason = (f"request n={n} exceeds service "
                          f"max_n={self.spec.n}")
            elif (request.rid in self.sched.tickets
                  or request.rid in self.slot_rid
                  or request.rid in self.results):
                # slot_rid/results cover in-flight and finished rids from
                # pre-ticket checkpoints, which carry no ticket table.
                reason = f"duplicate request id {request.rid}"
            elif (request.deadline_rounds is not None
                  and request.deadline_rounds < 1):
                reason = (f"deadline_rounds must be >= 1, got "
                          f"{request.deadline_rounds}")
            elif request.node_budget is not None and request.node_budget < 1:
                reason = f"node_budget must be >= 1, got {request.node_budget}"
        if reason is not None:
            self._emit("reject", rid=request.rid, reason=reason)
            if self._collector is not None:
                self._collector.lifecycle("reject", round_no=self.rounds,
                                          rid=request.rid, reason=reason)
            raise AdmissionError(reason)
        return self.sched.enqueue(request, now_round=self.rounds,
                                  service=self)

    def cancel(self, rid: int) -> bool:
        """Cancel request ``rid`` (the ``Ticket.cancel`` implementation).

        QUEUED: removed from the admission queue.  RUNNING: the slot is
        freed and its lanes reclaimed immediately — within one round — and
        the best-so-far is recorded as an anytime result.  Returns False
        for unknown or already-terminal rids.
        """
        ticket = self.sched.tickets.get(rid)
        if ticket is None or ticket.status in TERMINAL:
            return False
        best = None
        if ticket.status is TicketStatus.QUEUED:
            self.sched.remove_queued(rid)
        else:
            result = self._evict_slot(self.slot_rid.index(rid), "cancelled")
            best = result.optimum
        self.sched.resolve(rid, TicketStatus.CANCELLED, self.rounds)
        self._emit("cancel", rid=rid, best=best)
        self._note_lifecycle("cancel", rid, best=best)
        return True

    def _emit(self, kind: str, **kw) -> None:
        # One emission path for both drivers (repro.solver.emit): kind is
        # validated against EVENT_KINDS, so typos raise instead of flowing.
        from repro.solver import emit
        emit(self.on_event, kind, round=self.rounds, **kw)

    def _note_lifecycle(self, kind: str, rid: int,
                        best: Optional[int] = None) -> None:
        """Trace a terminal request transition with its wait/run rounds."""
        if self._collector is None:
            return
        ticket = self.sched.tickets.get(rid)
        self._collector.lifecycle(
            kind, round_no=self.rounds, rid=rid, best=best,
            waited=ticket.wait_rounds if ticket is not None else None,
            ran=ticket.run_rounds if ticket is not None else None)

    def _host_lane_fields(self):
        l = self.lanes
        return {
            "idx": np.asarray(l.idx).copy(),
            "depth": np.asarray(l.depth).copy(),
            "base": np.asarray(l.base).copy(),
            "inst": np.asarray(l.inst).copy(),
            "active": np.asarray(l.active).copy(),
            "t_s": np.asarray(l.t_s).copy(),
            "best": np.asarray(l.best).copy(),
        }

    def _admit_and_place(self) -> bool:
        """Admit queued requests into free slots and (re)target idle lanes.

        Admission ORDER is the scheduling policy's (priority heap by
        default); this method only supplies the mechanics.  Returns True
        when lane control state changed (stacks need replay).
        """
        # Steady-state fast path: nothing to drain/admit and every idle
        # lane already points at its round-robin live slot — skip the full
        # host round-trip (only ``active``/``inst`` are needed to decide).
        if not self.pool and not (len(self.sched)
                                  and any(r < 0 for r in self.slot_rid)):
            if self._placement_clean:
                return False             # no device readback at all
            live = [s for s in range(self.spec.k) if self.slot_rid[s] >= 0]
            # One-time validation readback after a placement-changing
            # event; with ≤1 live slot the jitted round can only move
            # lanes between idle-on and active-on that slot, so a passed
            # check stays true until the next host-side event.
            # repro-lint: disable=trace-safety -- event-driven: guarded by _placement_clean, not per-round
            active = np.asarray(self.lanes.active)
            # repro-lint: disable=trace-safety -- event-driven: guarded by _placement_clean, not per-round
            inst = np.asarray(self.lanes.inst)
            idle = np.flatnonzero(~active)
            wants = [live[j % len(live)] if live else NO_INSTANCE
                     for j in range(len(idle))]
            if all(inst[lane] == want for lane, want in zip(idle, wants)):
                self._placement_clean = len(live) <= 1
                return False

        h = self._host_lane_fields()
        idle = [i for i in range(self.num_lanes) if not h["active"][i]]
        changed = False

        # Pending-pool drain first: restored tasks have priority over fresh
        # roots for idle lanes (they are already-owned subtrees).
        while self.pool and idle:
            task = self.pool.pop(0)
            lane = idle.pop(0)
            il = h["idx"].shape[1]
            width = min(il, task.idx.shape[0])
            h["idx"][lane, :] = int(UNVISITED)
            h["idx"][lane, :width] = task.idx[:width]
            h["depth"][lane], h["base"][lane] = task.depth, task.base
            h["inst"][lane], h["active"][lane] = task.inst, True
            h["t_s"][lane] += 1
            changed = True

        # Admission: one free slot + one idle lane per popped request.
        free = [s for s in range(self.spec.k) if self.slot_rid[s] < 0]
        payload_host = None
        while len(self.sched) and free and idle:
            item = self.sched.pop_admission()
            if item is None:
                break
            req = item.request
            slot = free.pop(0)
            lane = idle.pop(0)
            # Family-oblivious packing: the registered spec carries the
            # stacked-table encoding (family id included in its return).
            adj, fm, fam = registry.get(req.family).pack(req.graph,
                                                         self.spec.n)
            self.tables.adj[slot] = adj
            self.tables.fullm[slot] = fm
            self.tables.family[slot] = fam
            self._touch_tables()
            self.slot_rid[slot] = req.rid
            self.slot_admitted[slot] = self.rounds
            self._slot_best_seen[slot] = int(INF_VALUE)
            ticket = self.sched.tickets.get(req.rid)
            if ticket is not None:
                ticket.status = TicketStatus.RUNNING
                ticket.admitted_round = self.rounds
            # Reset the slot incumbent, seed the root on the chosen lane.
            h["best"][slot] = int(INF_VALUE)
            if payload_host is None:
                payload_host = jax.tree_util.tree_map(
                    lambda p: np.asarray(p).copy(), self.lanes.best_payload)
            payload_host = jax.tree_util.tree_map(
                lambda p: _zero_row(p, slot), payload_host)
            h["idx"][lane, :] = int(UNVISITED)
            h["depth"][lane] = h["base"][lane] = 0
            h["inst"][lane], h["active"][lane] = slot, True
            h["t_s"][lane] += 1
            changed = True
            self._emit("admit", rid=req.rid)
            if self._collector is not None:
                self._collector.lifecycle(
                    "admit", round_no=self.rounds, rid=req.rid, slot=slot,
                    waited=(ticket.wait_rounds if ticket is not None
                            else None))

        # Retarget remaining idle lanes round-robin over live slots so the
        # next steal round can feed them (instance-scoped thieves).
        live = [s for s in range(self.spec.k) if self.slot_rid[s] >= 0]
        retargeted = False
        for j, lane in enumerate(idle):
            want = live[j % len(live)] if live else NO_INSTANCE
            if h["inst"][lane] != want:
                h["inst"][lane] = want   # no stack impact: lane stays idle
                retargeted = True

        if not changed and not retargeted:
            # Placement verified against the full host mirror: single-
            # tenant steady state can skip even the validation readback.
            self._placement_clean = len(live) <= 1
            return False                 # steady state: no host->device copy
        self.lanes = self._place(self.lanes._replace(
            idx=jnp.asarray(h["idx"]), depth=jnp.asarray(h["depth"]),
            base=jnp.asarray(h["base"]), inst=jnp.asarray(h["inst"]),
            active=jnp.asarray(h["active"]), t_s=jnp.asarray(h["t_s"]),
            best=jnp.asarray(h["best"]),
            best_payload=(self.lanes.best_payload if payload_host is None
                          else jax.tree_util.tree_map(jnp.asarray,
                                                      payload_host))))
        if changed:
            # CONVERTINDEX replay rebuilds the stacks of seeded/installed
            # lanes (replaying untouched active lanes is a no-op by the
            # determinism contract).
            self.lanes = self._rebuild(self.lanes, self._tables_jnp())
        # The host mirror h was just written to device, with idle lanes
        # retargeted to their round-robin wants by construction.
        self._placement_clean = len(live) <= 1
        return changed

    # -- retirement / eviction ----------------------------------------------

    def _retire(self, open_vec: np.ndarray) -> None:
        h_inst = None
        for slot in range(self.spec.k):
            rid = self.slot_rid[slot]
            if rid < 0 or open_vec[slot] != 0:
                continue
            if any(t.inst == slot for t in self.pool):
                continue                      # restored work still pending
            payload = jax.tree_util.tree_map(
                lambda p: np.asarray(p[slot]), self.lanes.best_payload)
            self.results[rid] = RequestResult(
                rid=rid,
                optimum=int(np.asarray(self.lanes.best)[slot]),
                payload=payload,
                admitted_round=self.slot_admitted[slot],
                retired_round=self.rounds)
            self.sched.resolve(rid, TicketStatus.DONE, self.rounds)
            self._emit("retire", rid=rid, best=self.results[rid].optimum)
            self._note_lifecycle("retire", rid,
                                 best=self.results[rid].optimum)
            self.slot_rid[slot] = -1
            # Unbind the retired slot's (now idle) lanes.
            if h_inst is None:
                # repro-lint: disable=trace-safety -- event-driven: only when a slot actually retires this round
                h_inst = np.asarray(self.lanes.inst).copy()
            h_inst[h_inst == slot] = NO_INSTANCE
        if h_inst is not None:
            self.lanes = self._place(
                self.lanes._replace(inst=jnp.asarray(h_inst)))
            self._placement_clean = False

    def _evict_slot(self, slot: int, status: str) -> RequestResult:
        """Free a slot mid-flight: record the best-so-far as an anytime
        result, then reclaim its lanes through the retire path's unbinding
        — extended to still-active lanes, which are deactivated (their
        subtrees are abandoned with the request) — and drop its
        pending-pool tasks.  The slot is reusable by the very next
        admission, i.e. eviction frees capacity within one round."""
        rid = self.slot_rid[slot]
        payload = jax.tree_util.tree_map(
            lambda p: np.asarray(p[slot]).copy(), self.lanes.best_payload)
        result = RequestResult(
            rid=rid,
            optimum=int(np.asarray(self.lanes.best)[slot]),
            payload=payload,
            admitted_round=self.slot_admitted[slot],
            retired_round=self.rounds,
            status=status)
        self.results[rid] = result
        self.slot_rid[slot] = -1
        self._placement_clean = False
        # repro-lint: disable=trace-safety -- event-driven: eviction only, not on the per-round path
        inst = np.asarray(self.lanes.inst).copy()
        # repro-lint: disable=trace-safety -- event-driven: eviction only, not on the per-round path
        active = np.asarray(self.lanes.active).copy()
        mine = inst == slot
        active[mine] = False
        inst[mine] = NO_INSTANCE
        self.lanes = self._place(self.lanes._replace(
            inst=jnp.asarray(inst), active=jnp.asarray(active)))
        self.pool = [t for t in self.pool if t.inst != slot]
        return result

    def _expire(self) -> None:
        """End-of-round deadline/budget sweep (the scheduler decides WHO,
        this method does the surgery)."""
        queued, running = self.sched.overdue(self.rounds)
        for rid in queued:
            self.sched.remove_queued(rid)
            self.sched.resolve(rid, TicketStatus.EXPIRED, self.rounds)
            # Never admitted: the anytime result is the empty incumbent.
            self.results[rid] = RequestResult(
                rid=rid, optimum=int(INF_VALUE),
                payload=jax.tree_util.tree_map(
                    lambda p: np.zeros_like(np.asarray(p)[0]),
                    self.lanes.best_payload),
                admitted_round=-1, retired_round=self.rounds,
                status="expired")
            self._emit("expire", rid=rid)
            self._note_lifecycle("expire", rid)
        for rid in running:
            result = self._evict_slot(self.slot_rid.index(rid), "expired")
            self.sched.resolve(rid, TicketStatus.EXPIRED, self.rounds)
            self._emit("expire", rid=rid, best=result.optimum)
            self._note_lifecycle("expire", rid, best=result.optimum)

    def _emit_incumbents(self) -> None:
        """Per-request anytime incumbent stream: one ``incumbent`` event
        each time a slot's bound improves.  Only costs the device readback
        when someone is listening."""
        if self.on_event is None:
            return
        best = np.asarray(self.lanes.best)
        for slot in range(self.spec.k):
            rid = self.slot_rid[slot]
            if rid >= 0 and int(best[slot]) < self._slot_best_seen[slot]:
                self._slot_best_seen[slot] = int(best[slot])
                self._emit("incumbent", rid=rid, best=int(best[slot]))

    # -- the service loop ---------------------------------------------------

    def _has_work(self) -> bool:
        return (len(self.sched) > 0 or bool(self.pool)
                or any(r >= 0 for r in self.slot_rid))

    def step_round(self) -> np.ndarray:
        """One service cycle: admit → round → retire → evict.
        Returns the per-slot open-work vector."""
        track = self.sched.track_nodes()
        col = self._collector
        changed = self._admit_and_place()
        if col is not None:
            # Host-side surgery (admission seeds, pool installs) bumps t_s
            # — refresh the baseline so steal deltas cover the jitted
            # round only.
            col.before_round(self.lanes, dirty=changed)
            nodes_before = None
        else:
            nodes_before = (np.asarray(self.lanes.nodes).copy()
                            if track else None)
        lanes, open_vec = self._round(self.lanes, self._tables_jnp())
        self.lanes = lanes
        self.rounds += 1
        open_np = np.asarray(open_vec)
        inst_delta = None
        if col is not None:
            inst_delta = col.after_round(
                self.rounds, self.lanes, int(open_np.sum()),
                queue_depth=self.sched.queue_depth(),
                slot_rids=self.slot_rid)
        if track:
            # Round-granular attribution: a lane's node delta this round is
            # charged to the instance it serves at the round boundary.
            # The collector computes exactly this delta already — reuse it
            # rather than paying a second readback.
            if inst_delta is None:
                delta = np.asarray(self.lanes.nodes) - nodes_before
                # repro-lint: disable=trace-safety -- deliberate: node-attribution fallback only when tracking without a collector
                inst = np.asarray(self.lanes.inst)
                inst_delta = np.zeros((self.spec.k,), np.int64)
                for slot in range(self.spec.k):
                    inst_delta[slot] = int(delta[inst == slot].sum())
            for slot in range(self.spec.k):
                rid = self.slot_rid[slot]
                if rid >= 0 and inst_delta[slot]:
                    self.sched.note_nodes(rid, int(inst_delta[slot]))
        self._emit("round", open_work=int(open_np.sum()),
                   metrics=(col.snapshot()
                            if col is not None and self.metrics_enabled
                            and self.on_event is not None else None))
        self._emit_incumbents()
        self._retire(open_np)
        self._expire()
        self.maybe_autoscale()
        return open_np

    def drain(self, max_rounds: int = 100000) -> Dict[int, RequestResult]:
        """Step rounds until every submitted request is terminal."""
        start = self.rounds
        while self._has_work():
            if self.rounds - start >= max_rounds:
                raise RuntimeError(
                    f"service did not drain in {max_rounds} rounds; "
                    f"slots={self.slot_rid} queue={len(self.queue)}")
            self.step_round()
        self.finalize_trace()
        return self.results

    def run(self, requests: Optional[List[SolveRequest]] = None,
            max_rounds: int = 100000) -> Dict[int, RequestResult]:
        """Deprecated batch-era drain: admit ``requests`` plus anything
        queued, solve them all.  ``submit()`` now returns a Ticket — use
        ``Ticket.result()`` per request or :meth:`drain` for the pool.
        Bitwise-identical to the ticketed path on the default policy."""
        warnings.warn(
            "SolverService.run() is deprecated; submit() returns a Ticket "
            "— use Ticket.result(), or SolverService.drain()",
            DeprecationWarning, stacklevel=2)
        for r in requests or []:
            self.submit(r)
        return self.drain(max_rounds)

    # -- elastic mesh membership --------------------------------------------

    def resize(self, *, mesh: Optional[Mesh] = None,
               num_lanes: Optional[int] = None) -> None:
        """Re-layout the live pool onto a different mesh / per-device lane
        count mid-run (the join-leave half of paper §VII, in memory).

        Goes through the elastic W' ≠ W checkpoint/restore machinery
        (``repro.core.checkpoint.repartition``): the first W' in-flight
        tasks land on the new lanes, surplus parks in the instance-tagged
        pending pool, per-instance incumbents and aggregate counters are
        carried over exactly.  Tickets, results, queue and tables stay
        live in place — outstanding :class:`Ticket` handles keep working.
        The round closure is re-jitted for the new mesh (the one real cost
        — which is why :class:`AutoscalePolicy` carries a cooldown).
        """
        per_dev = (self.lanes_per_device if num_lanes is None
                   else int(num_lanes))
        n_dev = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
        total = per_dev * n_dev
        if total < 1:
            raise ValueError(f"resize to {total} lanes")
        old_dev, old_total = self.n_devices, self.num_lanes
        problem = self.spec.bind(self._tables_host(), self.backend)
        lanes_host = _gather_lanes(self.lanes)
        new_lanes, surplus = ckpt.repartition(problem, lanes_host, total)
        self.mesh = mesh
        self.n_devices = n_dev
        self.lanes_per_device = per_dev
        self.num_lanes = total
        self._touch_tables()                   # re-place on the new mesh
        self.lanes = self._place(new_lanes)
        self.pool.extend(surplus)
        self._placement_clean = False
        self._build_round_fns()
        if self._collector is not None:
            self._collector.resize(total, devices=n_dev,
                                   round_no=self.rounds)
        self._emit("resize", reason=f"devices {old_dev}->{n_dev}, "
                                    f"lanes {old_total}->{total}")

    def maybe_autoscale(self) -> bool:
        """Ask the :class:`AutoscalePolicy` (when configured) whether to
        change the device count; perform the :meth:`resize` if so.  Runs
        once per round from :meth:`step_round` — the semi-centralized
        scheduler layer's elasticity hook."""
        if self.autoscale is None:
            return False
        target = self.autoscale.decide(
            queue_depth=self.sched.queue_depth(), devices=self.n_devices,
            now_round=self.rounds,
            busy=any(r >= 0 for r in self.slot_rid) or bool(self.pool))
        if target is None or target == self.n_devices:
            return False
        devices = jax.devices()
        if target > len(devices):
            return False
        mesh = (jax.make_mesh((target,), ("workers",),
                              devices=devices[:target])
                if target > 1 else None)
        self.resize(mesh=mesh)
        return True

    # -- elastic checkpoint -------------------------------------------------

    def save(self, path: str) -> None:
        """Persist lanes + slot tables + pending pool + the queued-request
        heap + ticket states in one atomic file.

        An un-drained service round-trips: queued (never-admitted)
        requests are stored with their graphs and admission sequence
        numbers so the restored policy heap pops in the saved order, and
        every ticket's lifecycle state (status, deadlines, budgets, node
        usage) is carried in a JSON sidecar array
        (``repro.core.checkpoint.pack_json``).

        Queued-instance persistence assumes graph-shaped instances (the
        same assumption the stacked tables themselves make — ``pack``
        returns adjacency rows); a future non-graph servable family needs
        a registry-provided encode/decode hook here.
        """
        pool_n = len(self.pool)
        il = self.lanes.idx.shape[1]
        pool_idx = np.full((pool_n, il), int(UNVISITED), np.int8)
        pool_meta = np.zeros((pool_n, 3), np.int32)     # depth, base, inst
        for i, t in enumerate(self.pool):
            width = min(il, t.idx.shape[0])
            pool_idx[i, :width] = t.idx[:width]
            pool_meta[i] = (t.depth, t.base, t.inst)

        pending = self.sched.pending()
        queue_adj = np.zeros((len(pending), self.spec.n,
                              num_words(self.spec.n)), np.uint32)
        queue_meta = []
        for i, item in enumerate(pending):
            g = item.request.graph
            queue_adj[i, :g.n, :g.words] = g.adj
            queue_meta.append({
                "rid": item.request.rid, "family": item.request.family,
                "name": g.name, "n": g.n, "seq": item.seq,
                "priority": item.request.priority,
                "deadline_rounds": item.request.deadline_rounds,
                "node_budget": item.request.node_budget,
            })
        done = sorted(self.results.values(), key=lambda r: r.rid)
        result_payload = (np.stack([np.asarray(r.payload) for r in done])
                          if done else np.zeros((0,), np.uint32))
        sched_meta = {
            "scheduler": self.sched.policy.name,
            "seq": self.sched.seq,
            "queue": queue_meta,
            "tickets": [{
                "rid": t.rid, "status": t.status.value,
                "priority": t.priority, "deadline_round": t.deadline_round,
                "node_budget": t.node_budget,
                "submitted_round": t.submitted_round,
                "admitted_round": t.admitted_round,
                "finished_round": t.finished_round,
                "nodes_used": t.nodes_used,
            } for t in self.sched.tickets.values()],
            "results": [{
                "rid": r.rid, "optimum": r.optimum,
                "admitted_round": r.admitted_round,
                "retired_round": r.retired_round, "status": r.status,
            } for r in done],
        }
        extra = {
            "adj": self.tables.adj, "fullm": self.tables.fullm,
            "family": self.tables.family,
            "slot_rid": np.asarray(self.slot_rid, np.int32),
            "slot_admitted": np.asarray(self.slot_admitted, np.int32),
            "spec": np.asarray([self.spec.n, self.spec.k], np.int32),
            "rounds": np.asarray(self.rounds, np.int32),
            "slot_best_seen": np.asarray(self._slot_best_seen, np.int32),
            "pool_idx": pool_idx, "pool_meta": pool_meta,
            "queue_adj": queue_adj,
            "result_payload": result_payload,
            "sched_meta": ckpt.pack_json(sched_meta),
        }
        ckpt.save(path, self.lanes, extra=extra)

    @classmethod
    def restore(cls, path: str, *, num_lanes: int,
                steps_per_round: int = 64, backend: str = "jnp",
                scheduler: Optional[Union[str, SchedulingPolicy]] = None,
                mesh: Optional[Mesh] = None, max_ship: int = 16,
                trace_path: Optional[str] = None, metrics: bool = False
                ) -> "SolverService":
        """Rebuild the service onto ``num_lanes`` lanes per device
        (elastic W' ≠ W; ``mesh`` — like the lane count and backend — is
        an execution choice, so a service saved single-device restores
        sharded and vice versa).

        Surplus in-flight tasks wait in the pending pool and are installed
        as lanes free up.  Queued (never-admitted) requests ARE persisted
        with their admission sequence, so the restored policy heap pops in
        the saved order; every ticket's state (including terminal ones)
        round-trips, with restored tickets re-bound to the new service.
        ``backend`` (like ``num_lanes``) is an execution choice, not
        checkpoint state: a service saved under one backend restores under
        any other with a bitwise-identical search (DESIGN.md §5.3), and
        ``scheduler`` defaults to the checkpointed policy but may be
        overridden — the queue is re-pushed through the new policy.
        """
        extra = ckpt.read_extra(path)
        n, k = (int(x) for x in extra["spec"])
        meta = (ckpt.unpack_json(extra["sched_meta"])
                if "sched_meta" in extra else
                {"scheduler": "priority", "seq": 0, "queue": [],
                 "tickets": [], "results": []})
        svc = cls._create(max_n=n, slots=k, num_lanes=num_lanes,
                          steps_per_round=steps_per_round, backend=backend,
                          scheduler=(meta["scheduler"] if scheduler is None
                                     else scheduler),
                          mesh=mesh, max_ship=max_ship,
                          trace_path=trace_path, metrics=metrics)
        svc.tables = StackedTables(
            adj=extra["adj"].copy(), fullm=extra["fullm"].copy(),
            family=extra["family"].copy())
        svc._touch_tables()
        problem = svc.spec.bind(svc._tables_host(), backend)
        lanes, svc.pool = ckpt.restore(path, problem, svc.num_lanes)
        svc.lanes = svc._place(lanes)
        for i in range(extra["pool_idx"].shape[0]):
            d, b, inst = (int(x) for x in extra["pool_meta"][i])
            svc.pool.append(ckpt.PendingTask(extra["pool_idx"][i].copy(),
                                             d, b, inst))
        svc.slot_rid = [int(r) for r in extra["slot_rid"]]
        svc.slot_admitted = [int(r) for r in extra["slot_admitted"]]
        svc.rounds = int(extra["rounds"])
        if svc._collector is not None:
            # Re-baseline on the restored lanes so the first round's deltas
            # exclude the carried checkpoint totals.
            svc._collector.start(svc.lanes)
        if "slot_best_seen" in extra:     # keep the incumbent stream exact
            svc._slot_best_seen = [int(b) for b in extra["slot_best_seen"]]

        for t in meta["tickets"]:
            svc.sched.adopt(Ticket(
                rid=t["rid"], priority=t["priority"],
                deadline_round=t["deadline_round"],
                node_budget=t["node_budget"],
                status=TicketStatus(t["status"]),
                submitted_round=t["submitted_round"],
                admitted_round=t["admitted_round"],
                finished_round=t["finished_round"],
                nodes_used=t["nodes_used"], _service=svc))
        for i, q in enumerate(meta["queue"]):
            graph = Graph(n=q["n"],
                          adj=extra["queue_adj"][i, :q["n"],
                                                 :num_words(q["n"])].copy(),
                          name=q["name"])
            svc.sched.policy.push(QueueItem(q["seq"], SolveRequest(
                rid=q["rid"], graph=graph, family=q["family"],
                priority=q["priority"],
                deadline_rounds=q["deadline_rounds"],
                node_budget=q["node_budget"])))
        svc.sched.seq = int(meta["seq"])
        for i, r in enumerate(meta["results"]):
            svc.results[r["rid"]] = RequestResult(
                rid=r["rid"], optimum=r["optimum"],
                payload=extra["result_payload"][i].copy(),
                admitted_round=r["admitted_round"],
                retired_round=r["retired_round"], status=r["status"])
        return svc


def _zero_row(arr: np.ndarray, row: int) -> np.ndarray:
    arr[row] = np.zeros_like(arr[row])
    return arr


def make_service_round_fns(spec: StackedSpec, backend: str,
                           steps_per_round: int, *, fused_steps: int = 1,
                           mesh: Optional[Mesh] = None, max_ship: int = 16):
    """The service's two jitted closures over ``(lanes, tables)``: the
    round (expand → instance-scoped steal → per-instance open work) and
    the CONVERTINDEX stack rebuild run after host-side admission.

    With a mesh both run per device under the same specs — lanes sharded
    over every mesh axis, stacked tables replicated; the replay is
    lane-local, so each device rebuilds its own lanes' stacks.
    """
    def _rebuild(lanes, tables):
        return ckpt.rebuild_stacks(spec.bind(tables, backend), lanes)

    if mesh is None:
        def _round(lanes, tables):
            return make_round(spec.bind(tables, backend), steps_per_round,
                              fused_steps=fused_steps)(lanes)

        return jax.jit(_round), jax.jit(_rebuild)
    axes = tuple(mesh.axis_names)

    def _round(lanes, tables):
        return make_round(spec.bind(tables, backend), steps_per_round, axes,
                          max_ship, fused_steps)(lanes)

    proto = spec.bind(StackedTables(*(jnp.asarray(t)
                                      for t in spec.empty_tables())), backend)
    lane_specs = lane_partition_specs(proto, axes)
    table_specs = StackedTables(P(), P(), P())
    rebuild = jax.jit(jax.shard_map(
        _rebuild, mesh=mesh, in_specs=(lane_specs, table_specs),
        out_specs=lane_specs, check_vma=False))
    round_fn = jax.jit(jax.shard_map(
        _round, mesh=mesh, in_specs=(lane_specs, table_specs),
        out_specs=(lane_specs, P()), check_vma=False))
    return round_fn, rebuild
