"""The Solver session API — one front door for serial, distributed and
service solves.

The paper's framework has three execution paths (a serial oracle, the
distributed BSP engine, and the multi-tenant solver service) which used to
be driven by three divergent call surfaces: a 12-kwarg
``core.distributed.solve``, a ``SolverService.__init__`` with its own
kwargs, and hand-rolled ``serial_rb`` calls.  This module replaces all
three with one session object (DESIGN.md §6)::

    cfg = SolverConfig(lanes=64, steps_per_round=64, backend="pallas")
    solver = Solver(cfg)

    res = solver.solve(registry.problem("vc", "reg:48:4:1"))   # distributed
    ref = solver.oracle(registry.problem("vc", "reg:48:4:1"))  # serial
    svc = solver.serve(max_n=32, slots=4)                      # service
    assert res.stats.best == ref.best

``SolverConfig`` is frozen and validated at construction; problem-dependent
checks (kernel-backend capabilities, checkpoint compatibility) happen when
the config first meets a problem.  Progress reporting is a typed
:class:`ProgressEvent` stream (``on_event``) shared by the distributed
driver and the service driver — the generalization of the old ``on_round``
callback.

The legacy entry points (``repro.core.distributed.solve(...)`` kwargs and
direct ``SolverService(...)`` construction) remain as thin shims over this
module and emit ``DeprecationWarning``; results are bitwise-identical
because both run the exact same round loop below.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import obs
from repro import registry as _registry
from repro.core.api import BinaryProblem
from repro.core.distributed import (SolveStats, _gather_lanes, _shard_lanes,
                                    jit_round_loop, make_round)
from repro.core.engine import Lanes, init_lanes
from repro.core.serial import serial_rb

__all__ = [
    "ConfigError",
    "EVENT_KINDS",
    "OracleResult",
    "ProgressEvent",
    "SolveResult",
    "Solver",
    "SolverConfig",
    "SolveStats",
    "emit",
]


class ConfigError(ValueError):
    """An invalid :class:`SolverConfig`, or one incompatible with the
    problem it is being applied to."""


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Frozen execution policy for a solver session.

    Attributes:
      lanes: engine lanes per device (total lanes = lanes × #devices).
      steps_per_round: engine steps between steal/collective phases (R).
      max_rounds: hard round budget before the drive aborts.
      mesh: device mesh, or None (single device) — honored by both
        :meth:`Solver.solve` and the sharded service (:meth:`Solver.serve`).
      max_ship: cross-device tasks shipped per device per round.
      bootstrap_rounds / bootstrap_steps: short ramp-up rounds that flood
        initial tasks (the paper's GETPARENT topology analogue).
      backend: node-evaluation kernel backend ("jnp" | "pallas"), validated
        against the problem family's registered capabilities at build time.
      checkpoint_every / checkpoint_path: periodic checkpointing policy
        (``checkpoint_every > 0`` requires a path).
      resume_from: checkpoint to restore before solving (elastic: any lane
        count; the instance-slot count must match the problem).
      scheduler: service admission policy name ("priority" | "sjf" |
        "fifo" — ``repro.service.scheduler.SCHEDULERS``), validated
        against the registered policies when the config meets
        :meth:`Solver.serve`.
      fused_steps: engine steps fused per expand-loop iteration (S; the
        multi-step round kernel of DESIGN.md §5.5).  Tree-identical for
        any S — it only amortizes per-step dispatch — so it is a pure
        execution knob like ``backend``.
      trace_path: write a JSONL telemetry trace here (``repro.obs.trace``
        schema; render with ``tools/trace_report.py``).  Collection is
        host-side from values the round loop already materializes, so the
        search tree is bit-identical with tracing on or off (DESIGN.md
        §8).
      metrics: collect an in-process metrics registry, queryable as a
        ``MetricsSnapshot`` via ``Solver.metrics()`` /
        ``SolverService.metrics()`` and attached to "round"/"done"
        :class:`ProgressEvent`\\ s.  Same host-side-only guarantee as
        ``trace_path``.
      autoscale: an ``repro.service.scheduler.AutoscalePolicy`` (or None)
        — service mode only.  Each round the driver asks the policy for a
        target device count keyed on the admission queue depth and
        resizes the mesh elastically (``SolverService.resize``, an
        in-memory W' ≠ W checkpoint/restore).  Ignored by
        :meth:`Solver.solve`, whose device count is fixed by ``mesh``.
    """

    lanes: int = 32
    steps_per_round: int = 64
    max_rounds: int = 100000
    mesh: Optional[Mesh] = None
    max_ship: int = 16
    bootstrap_rounds: int = 0
    bootstrap_steps: int = 8
    backend: str = "jnp"
    checkpoint_every: int = 0
    checkpoint_path: Optional[str] = None
    resume_from: Optional[str] = None
    scheduler: str = "priority"
    fused_steps: int = 1
    trace_path: Optional[str] = None
    metrics: bool = False
    autoscale: Optional[Any] = None

    def __post_init__(self):
        if self.lanes < 1:
            raise ConfigError(f"lanes must be >= 1, got {self.lanes}")
        if self.steps_per_round < 1:
            raise ConfigError(
                f"steps_per_round must be >= 1, got {self.steps_per_round}")
        if self.max_ship < 1:
            raise ConfigError(f"max_ship must be >= 1, got {self.max_ship}")
        if self.bootstrap_rounds < 0 or self.bootstrap_steps < 1:
            raise ConfigError(
                f"bad bootstrap policy: rounds={self.bootstrap_rounds} "
                f"steps={self.bootstrap_steps}")
        if self.checkpoint_every < 0:
            raise ConfigError(
                f"checkpoint_every must be >= 0, got {self.checkpoint_every}")
        if self.checkpoint_every and not self.checkpoint_path:
            raise ConfigError(
                "checkpoint_every > 0 requires checkpoint_path")
        if not isinstance(self.backend, str) or not self.backend:
            raise ConfigError(f"backend must be a name, got {self.backend!r}")
        if not isinstance(self.scheduler, str) or not self.scheduler:
            raise ConfigError(
                f"scheduler must be a policy name, got {self.scheduler!r}")
        if self.fused_steps < 1:
            raise ConfigError(
                f"fused_steps must be >= 1, got {self.fused_steps}")
        if self.trace_path is not None and (
                not isinstance(self.trace_path, str) or not self.trace_path):
            raise ConfigError(
                f"trace_path must be a path, got {self.trace_path!r}")


#: Every ProgressEvent kind either driver may emit.  Frozen on purpose:
#: constructing an event with any other kind raises, so a typo'd kind
#: fails at the emitter instead of flowing silently past consumers.
EVENT_KINDS = frozenset({
    "round", "checkpoint", "admit", "incumbent", "retire", "reject",
    "cancel", "expire", "resize", "done",
})


@dataclasses.dataclass(frozen=True)
class ProgressEvent:
    """One typed progress notification from either driver.

    ``kind`` is one of:
      "round"      — a solve/service round finished (``round``, ``open_work``,
                     ``best``; solve rounds also carry ``lanes``);
      "checkpoint" — a checkpoint was written (``path``);
      "admit"      — the service admitted request ``rid`` into a slot;
      "incumbent"  — request ``rid``'s anytime incumbent improved to
                     ``best`` (the service's per-request progress stream);
      "retire"     — the service retired request ``rid`` (``best`` is its
                     optimum);
      "reject"     — ``submit()`` refused request ``rid`` (``reason``;
                     emitted just before the AdmissionError is raised);
      "cancel"     — request ``rid`` was cancelled (``best`` is the anytime
                     incumbent if it ever ran);
      "expire"     — request ``rid`` hit its deadline or node budget and
                     was evicted with ``best`` as its anytime result;
      "resize"     — the service re-laid its lane pool onto a different
                     mesh / lane count (``reason`` describes the change);
      "done"       — the solve drained (``best`` is the global optimum).

    ``metrics`` carries a ``repro.obs.MetricsSnapshot`` on "round"/"done"
    events when ``SolverConfig.metrics`` is set (None otherwise).
    """

    kind: str
    round: int
    open_work: int = 0
    best: Optional[int] = None
    rid: Optional[int] = None
    path: Optional[str] = None
    reason: Optional[str] = None
    lanes: Optional[Lanes] = None
    metrics: Optional[Any] = None

    def __post_init__(self):
        if self.kind not in EVENT_KINDS:
            raise ValueError(
                f"unknown ProgressEvent kind {self.kind!r} (known: "
                f"{', '.join(sorted(EVENT_KINDS))})")


#: Event-consumer signature shared by both drivers.
EventCallback = Callable[[ProgressEvent], None]


def emit(on_event: Optional[EventCallback], kind: str, **fields) -> None:
    """The ONE ProgressEvent emission path for both drivers.

    Validates ``kind`` against :data:`EVENT_KINDS` unconditionally (a
    typo'd kind raises even with nobody listening), then constructs and
    delivers the event only when a listener is attached — emission stays
    free on the hot path when ``on_event`` is None.
    """
    if kind not in EVENT_KINDS:
        raise ValueError(
            f"unknown ProgressEvent kind {kind!r} (known: "
            f"{', '.join(sorted(EVENT_KINDS))})")
    if on_event is not None:
        on_event(ProgressEvent(kind=kind, **fields))


class SolveResult(NamedTuple):
    """Outcome of :meth:`Solver.solve` (payload squeezed for K = 1)."""

    payload: Any
    stats: SolveStats
    lanes: Lanes


class OracleResult(NamedTuple):
    """Outcome of :meth:`Solver.oracle` (SERIAL-RB ground truth)."""

    best: int
    nodes: int


class Solver:
    """A solver session: one config, three execution paths.

    ``on_event`` (optional) receives :class:`ProgressEvent` records from
    whichever driver runs — the typed successor of the old ``on_round``
    callback, shared by :meth:`solve` and the service returned by
    :meth:`serve`.
    """

    def __init__(self, config: Optional[SolverConfig] = None,
                 on_event: Optional[EventCallback] = None):
        self.config = config or SolverConfig()
        self.on_event = on_event
        self._obs = None          # RoundCollector of the most recent solve

    def metrics(self):
        """``repro.obs.MetricsSnapshot`` of the most recent (or running)
        :meth:`solve`, or None when telemetry was off (enable with
        ``SolverConfig(metrics=True)`` or ``trace_path=...``)."""
        return self._obs.snapshot() if self._obs is not None else None

    # -- problem resolution -------------------------------------------------

    def _resolve(self, problem) -> BinaryProblem:
        """ProblemHandle -> BinaryProblem under the config's backend (with
        capability validation); a raw BinaryProblem passes through."""
        if isinstance(problem, _registry.ProblemHandle):
            try:
                # ProblemSpec.build owns the capability check; surface its
                # refusal as a config error (the backend came from config).
                return problem.build(backend=self.config.backend)
            except ValueError as e:
                raise ConfigError(str(e)) from e
        if isinstance(problem, BinaryProblem):
            return problem
        raise TypeError(
            f"expected a registry.ProblemHandle or BinaryProblem, got "
            f"{type(problem).__name__}")

    # -- serial reference ---------------------------------------------------

    def oracle(self, problem) -> OracleResult:
        """SERIAL-RB on the family's registered scalar oracle."""
        if isinstance(problem, _registry.ProblemHandle):
            py = problem.oracle()
        else:
            py = problem                   # an already-built PyProblem
        best, nodes, _ = serial_rb(py)
        return OracleResult(best=best, nodes=nodes)

    # -- the distributed / single-device drive ------------------------------

    def solve(self, problem) -> SolveResult:
        """Run rounds until global termination (the paper's PARALLEL-RB).

        ``problem`` is a :class:`repro.registry.ProblemHandle` (built under
        the config's backend) or an already-built ``BinaryProblem``.
        ``config.lanes`` is the per-device lane count; with ``mesh=None``
        the solve is single-device, otherwise rounds are the shard_map'd
        collective version over every mesh axis.

        ``resume_from`` restores a checkpoint written by any earlier run at
        ANY lane/device count (elastic restart, paper §VII): surplus tasks
        beyond the new lane count wait in a host-side pool and are
        installed into idle lanes at round boundaries.

        Rounds run in a device loop (``distributed.jit_round_loop``):
        one dispatch runs rounds until no work is open or its budget is
        spent, and one readback then fetches the open work and the rounds
        it ran.  The host needs a round back only to feed the resume pool,
        to hand it to ``on_event`` or the telemetry collector
        (``metrics``, ``trace_path``), or to checkpoint it.  So a dispatch
        runs one round while any of the first three is there, as far as
        the next checkpoint boundary with ``checkpoint_every``, and
        otherwise every round left to ``max_rounds``: a plain solve is one
        dispatch, plus one for its bootstrap rounds.  The rounds, and so
        the stats and the payload, are the same for every budget.

        The call's phases are host spans in the profiler's trace
        (``repro.obs.spans``): ``repro.solve.prepare``, one
        ``repro.solve.round`` per dispatch holding its
        ``repro.solve.dispatch`` and ``repro.solve.readback``, and
        ``repro.solve.finish``.
        """
        from repro.core import checkpoint as ckpt

        cfg = self.config
        with obs.span("repro.solve.prepare"):
            problem = self._resolve(problem)
            mesh = cfg.mesh
            bootstrap_rounds = cfg.bootstrap_rounds
            total_lanes = cfg.lanes * (1 if mesh is None
                                       else int(np.prod(mesh.devices.shape)))

            pool: list = []
            if cfg.resume_from is not None:
                if not os.path.exists(cfg.resume_from):
                    raise ConfigError(
                        f"resume_from checkpoint not found: {cfg.resume_from}")
                try:
                    lanes, pool = ckpt.restore(cfg.resume_from, problem,
                                               total_lanes)
                except ValueError as e:    # e.g. instance-slot mismatch
                    raise ConfigError(
                        f"resume_from {cfg.resume_from!r} is incompatible "
                        f"with this problem/config: {e}") from e
                bootstrap_rounds = max(bootstrap_rounds, 1)  # respread work
            else:
                lanes = init_lanes(problem, total_lanes)
            if mesh is not None:
                lanes = _shard_lanes(lanes, mesh)

            collector = on_trace = None
            if cfg.metrics or cfg.trace_path is not None:
                collector = obs.RoundCollector(
                    mode="solve", lanes=total_lanes,
                    slots=problem.num_instances,
                    steps_per_round=cfg.steps_per_round,
                    fused_steps=cfg.fused_steps, backend=cfg.backend,
                    trace=(obs.TraceWriter(cfg.trace_path)
                           if cfg.trace_path else None))
                collector.start(lanes)  # after restore: deltas = this run
                on_trace = collector.registry.counter(
                    "round_traces",
                    "times the round's Python body was traced").inc
            self._obs = collector

            # jit traces each new round when it is first called, in the
            # first dispatch; ``on_trace`` counts those traces.
            axes = () if mesh is None else tuple(mesh.axis_names)

            def build(steps):
                return jit_round_loop(
                    make_round(problem, steps, axes, cfg.max_ship,
                               fused_steps=cfg.fused_steps,
                               on_trace=on_trace),
                    problem, mesh)
            round_fn = build(cfg.steps_per_round)
            boot_fn = build(cfg.bootstrap_steps) if bootstrap_rounds else None

        def feed_pool(lanes):
            nonlocal pool
            if pool:
                lanes = _gather_lanes(lanes)
                lanes, pool = ckpt.install_pending(problem, lanes, pool)
                if mesh is not None:
                    lanes = _shard_lanes(lanes, mesh)
            return lanes

        def snap():
            return (collector.snapshot()
                    if collector is not None and cfg.metrics else None)

        # The host takes each round itself while it has a use for it.
        each_round = self.on_event is not None or collector is not None

        def dispatch(fn, lanes, rounds, limit):
            """Rounds ``rounds + 1`` on, at most ``limit``, in one
            dispatch: feed, dispatch, read back, collect."""
            fed = bool(pool)
            lanes = feed_pool(lanes)
            budget = 1 if each_round or pool else limit
            if collector is not None:
                collector.before_round(lanes, dirty=fed)
            with obs.span("repro.solve.dispatch"):
                lanes, open_work, ran = fn(lanes, np.int32(budget))
            with obs.span("repro.solve.readback"):
                open_work, ran = jax.device_get((open_work, ran))
            open_now, rounds = int(np.sum(open_work)), rounds + int(ran)
            if collector is not None:
                collector.after_round(rounds, lanes, open_now)
            return lanes, open_now, rounds

        rounds, done = 0, False
        while not done and rounds < bootstrap_rounds:
            with obs.span("repro.solve.round"):
                lanes, open_now, rounds = dispatch(
                    boot_fn, lanes, rounds, bootstrap_rounds - rounds)
            done = open_now == 0 and not pool
        every = cfg.checkpoint_every
        while not done and rounds < cfg.max_rounds:
            limit = cfg.max_rounds - rounds
            if every:
                limit = min(limit, every - rounds % every)
            with obs.span("repro.solve.round"):
                lanes, open_now, rounds = dispatch(round_fn, lanes, rounds,
                                                   limit)
                if self.on_event is not None:
                    # The incumbent readback costs a device sync — only pay
                    # it when someone is listening.
                    emit(self.on_event, "round", round=rounds,
                         open_work=open_now, best=int(jnp.min(lanes.best)),
                         lanes=lanes, metrics=snap())
                if every and rounds % every == 0:
                    ckpt.save(cfg.checkpoint_path, _gather_lanes(lanes))
                    emit(self.on_event, "checkpoint", round=rounds,
                         path=cfg.checkpoint_path)
            done = open_now == 0 and not pool

        with obs.span("repro.solve.finish"):
            stats = SolveStats(
                best=int(jnp.min(lanes.best)),
                rounds=rounds,
                nodes=int(jnp.sum(lanes.nodes)),
                t_s=int(jnp.sum(lanes.t_s)),
                t_r=int(jnp.sum(lanes.t_r)),
                donated=int(jnp.sum(lanes.donated)),
                lanes=int(lanes.active.shape[0]),
                t_c=int(jnp.sum(lanes.t_c)),
            )
            if collector is not None:
                collector.finish(rounds=rounds,
                                 best=[int(b) for b in np.asarray(lanes.best)])
                collector.close()
            emit(self.on_event, "done", round=rounds, open_work=0,
                 best=stats.best, metrics=snap())
            best_payload = jax.tree_util.tree_map(np.asarray,
                                                  lanes.best_payload)
        if problem.num_instances == 1:
            # Single-instance API: drop the K=1 incumbent-table dim.
            best_payload = jax.tree_util.tree_map(lambda p: p[0],
                                                  best_payload)
        return SolveResult(payload=best_payload, stats=stats, lanes=lanes)

    # -- the multi-tenant service -------------------------------------------

    def serve(self, *, max_n: int, slots: int):
        """The session-flavored :class:`repro.service.SolverService` under
        this config (lanes, steps_per_round, backend, scheduler) and event
        stream.

        Its ``submit()`` returns a :class:`repro.service.Ticket` — the
        future-like request handle with ``status`` / ``result(timeout=)``
        / ``cancel()`` (DESIGN.md §7); requests carry ``priority``,
        ``deadline_rounds`` and ``node_budget``, and admission order is
        the config's ``scheduler`` policy.  Any registered *servable*
        family (``ProblemSpec.servable``) can be submitted; admission is
        validated at ``submit()`` time (typed
        :class:`repro.service.AdmissionError`, after a ``reject`` event).

        With ``mesh`` set the service runs SHARDED (DESIGN.md §9): the
        lane pool is partitioned over the mesh (``lanes`` per device), the
        stacked tables and per-instance incumbents are replicated, rounds
        run under shard_map with instance-scoped cross-device stealing,
        and per-instance open-work/node accounting reduces across the mesh
        each round.  Admission stays a host-side table write either way.

        The service driver has its own checkpoint surface
        (``SolverService.save`` / ``.restore``), so a config carrying
        ``checkpoint_every`` or ``resume_from`` is rejected here rather
        than silently ignored.
        """
        from repro.service.batch_problem import STACKED_BACKENDS
        from repro.service.driver import SolverService
        from repro.service.scheduler import SCHEDULERS

        if self.config.backend not in STACKED_BACKENDS:
            raise ConfigError(
                f"backend {self.config.backend!r} is not supported by the "
                f"stacked service (supports: {', '.join(STACKED_BACKENDS)})")
        if self.config.scheduler not in SCHEDULERS:
            raise ConfigError(
                f"unknown scheduler {self.config.scheduler!r} (registered "
                f"policies: {', '.join(sorted(SCHEDULERS))})")
        unsupported = [
            name for name, is_set in (
                ("checkpoint_every", bool(self.config.checkpoint_every)),
                ("resume_from", self.config.resume_from is not None),
            ) if is_set]
        if unsupported:
            raise ConfigError(
                f"SolverConfig fields not honored by the service driver: "
                f"{', '.join(unsupported)} — use SolverService.save/restore "
                f"for service checkpoints")
        return SolverService.from_config(self.config, max_n=max_n,
                                         slots=slots, on_event=self.on_event)
