"""Multi-tenant solver service launcher: many instances, one lane pool.

  PYTHONPATH=src python -m repro.launch.serve_solver \
      --instances vc:gnp:20:30:5@prio=2,ds:gnp:16:30:7@deadline=60,vc:reg:24:4:1 \
      --lanes 32 --slots 4 [--scheduler sjf] [--backend pallas] \
      [--devices 4] [--autoscale 8] [--ckpt svc.ckpt] [--resume]

Each instance spec is ``<family>:<instance>[@<attr>=<v>...]`` where
``<family>`` is any *servable* registered problem family
(``repro.registry``) and ``<instance>`` uses that family's own registered
parser (``gnp:<n>:<p*100>:<seed>``, ``reg:<n>:<k>:<seed>``, ``cell60``).
Per-request lifecycle attributes ride after ``@`` separators:
``prio=<int>`` (admission priority under the priority scheduler),
``deadline=<rounds>`` (expire the request that many service rounds after
submission) and ``budget=<nodes>`` (evict after that many search nodes) —
e.g. ``vc:gnp:20:30:5@prio=3@deadline=80``.  ``--scheduler`` picks the
admission policy (``priority`` default, ``sjf``, ``fifo`` —
``repro.service.scheduler``).  ``--repeat R`` replays the whole mix R
times (distinct request ids) to exercise continuous batching past the
slot count.  ``--backend pallas`` routes the shared stacked evaluate
through the batched masked-popcount kernel (DESIGN.md §5.3) — results are
bitwise-identical to jnp.  ``--devices N`` shards the lane pool over the
first N devices (``--lanes`` is PER DEVICE; DESIGN.md §9) and
``--autoscale MAXDEV`` lets the service grow/shrink the mesh elastically
with the admission queue depth.

``submit()`` returns a Ticket per request; the drain loop reports each
ticket's terminal status (done / expired / cancelled) and its
submission-to-resolution latency in rounds.  The launcher contains zero
per-family branching: admission rules live in the registry +
``SolverService.submit`` (typed ``AdmissionError`` after a ``reject``
event), and the service is built through the :class:`repro.solver.Solver`
facade (DESIGN.md §6/§7).
"""

from __future__ import annotations

import argparse
import time

from repro import compile_cache, registry
from repro.service import SCHEDULERS, SolveRequest, SolverService
from repro.solver import Solver, SolverConfig

_ATTRS = {"prio": "priority", "deadline": "deadline_rounds",
          "budget": "node_budget"}


def parse_workload(spec: str, repeat: int):
    """-> list of (family, instance, lifecycle-kwargs) over the mix."""
    out = []
    for item in spec.split(","):
        body, *attrs = item.split("@")
        family, _, inst = body.partition(":")
        if not inst:
            raise SystemExit(
                f"bad instance spec {item!r}: want <family>:<instance>")
        try:
            pspec = registry.get(family)
        except registry.UnknownProblemError as e:
            raise SystemExit(f"bad instance spec {item!r}: {e}")
        if not pspec.servable:
            raise SystemExit(
                f"bad instance spec {item!r}: family {family!r} is not "
                f"servable (no service packing registered)")
        kwargs = {}
        for attr in attrs:
            key, _, val = attr.partition("=")
            if key not in _ATTRS or not val:
                raise SystemExit(
                    f"bad instance spec {item!r}: want @<attr>=<int> with "
                    f"attr in {sorted(_ATTRS)}, got {attr!r}")
            try:
                kwargs[_ATTRS[key]] = int(val)
            except ValueError:
                raise SystemExit(
                    f"bad instance spec {item!r}: {attr!r} is not an int")
        try:
            out.append((family, pspec.parse(inst), kwargs))
        except ValueError as e:
            raise SystemExit(f"bad instance spec {item!r}: {e}")
    return out * repeat


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances",
                    default="vc:gnp:20:30:5,ds:gnp:16:30:7,vc:reg:24:4:1,"
                            "ds:gnp:14:25:2")
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--scheduler", choices=sorted(SCHEDULERS), default=None,
                    help="admission policy (DESIGN.md §7; default: priority,"
                         " or the checkpointed policy with --resume)")
    ap.add_argument("--backend", choices=["jnp", "pallas"], default="jnp",
                    help="shared-evaluate kernel backend (DESIGN.md §5.3)")
    ap.add_argument("--steps-per-round", type=int, default=64)
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the lane pool over the first N devices "
                         "(--lanes is PER DEVICE; DESIGN.md §9)")
    ap.add_argument("--max-ship", type=int, default=16,
                    help="cross-device tasks shipped per device per round")
    ap.add_argument("--autoscale", type=int, default=0, metavar="MAXDEV",
                    help="grow/shrink the mesh elastically up to MAXDEV "
                         "devices, keyed on admission queue depth "
                         "(starts at --devices)")
    ap.add_argument("--ckpt", default=None,
                    help="service checkpoint path (written every "
                         "--ckpt-every rounds and after the drain)")
    ap.add_argument("--ckpt-every", type=int, default=10,
                    help="rounds between mid-run checkpoints (0 = final only)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the service from --ckpt before serving")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a JSONL service trace (repro.obs schema; "
                         "summarize with tools/trace_report.py)")
    ap.add_argument("--metrics", action="store_true",
                    help="collect in-process metrics and print a summary")
    args = ap.parse_args()
    compile_cache.enable()

    if args.resume and not args.ckpt:
        ap.error("--resume requires --ckpt")

    import jax

    from repro.service.scheduler import AutoscalePolicy

    if args.devices > len(jax.devices()):
        ap.error(f"--devices {args.devices} > available device count "
                 f"{len(jax.devices())} (force host devices with "
                 f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    mesh = (jax.make_mesh((args.devices,), ("workers",),
                          devices=jax.devices()[:args.devices])
            if args.devices > 1 else None)
    autoscale = (AutoscalePolicy(max_devices=args.autoscale)
                 if args.autoscale > 1 else None)

    workload = parse_workload(args.instances, args.repeat)
    if args.resume:
        svc = SolverService.restore(args.ckpt, num_lanes=args.lanes,
                                    steps_per_round=args.steps_per_round,
                                    backend=args.backend,
                                    scheduler=args.scheduler,
                                    mesh=mesh, max_ship=args.max_ship,
                                    trace_path=args.trace,
                                    metrics=args.metrics)
        svc.autoscale = autoscale
        print(f"restored service: slots={svc.slot_rid} "
              f"queue={len(svc.queue)} pool={len(svc.pool)} "
              f"rounds={svc.rounds} scheduler={svc.sched.policy.name}")
        # In-flight slots and the restored queue finish under their
        # checkpointed rids/tickets; the --instances workload is submitted
        # as NEW requests with rids past everything the checkpoint issued
        # (pre-ticket checkpoints carry no ticket table, so in-flight slot
        # rids count too).
        rid0 = 1 + max(list(svc.tickets)
                       + [r for r in svc.slot_rid if r >= 0] + [-1])
    else:
        max_n = max(registry.get(fam).size(g) for fam, g, _ in workload)
        config = SolverConfig(lanes=args.lanes,
                              steps_per_round=args.steps_per_round,
                              backend=args.backend,
                              scheduler=args.scheduler or "priority",
                              mesh=mesh, max_ship=args.max_ship,
                              autoscale=autoscale,
                              trace_path=args.trace, metrics=args.metrics)
        svc = Solver(config).serve(max_n=max_n, slots=args.slots)
        rid0 = 0
    reqs = [SolveRequest(rid=rid0 + i, graph=g, family=fam, **kwargs)
            for i, (fam, g, kwargs) in enumerate(workload)]
    tickets = {r.rid: svc.submit(r) for r in reqs}

    print(f"serving {len(reqs)} requests over {svc.num_lanes} lanes "
          f"({svc.n_devices} device(s) x {svc.lanes_per_device}) / "
          f"{svc.spec.k} slots (padded n={svc.spec.n}, "
          f"backend={svc.backend}, scheduler={svc.sched.policy.name})")
    t0 = time.time()
    while svc._has_work():
        svc.step_round()
        if (args.ckpt and args.ckpt_every
                and svc.rounds % args.ckpt_every == 0):
            svc.save(args.ckpt)
    wall = time.time() - t0
    svc.finalize_trace()          # manual step loop: write the summary row
    by_rid = {q.rid: q for q in reqs}
    # Pre-ticket checkpoints restore in-flight slots without tickets, so
    # report over tickets AND results.
    served = sorted(set(svc.tickets) | set(svc.results))
    for rid in served:
        ticket = svc.tickets.get(rid)
        req = by_rid.get(rid)
        label = (f"{req.family}[{req.graph.name}]" if req is not None
                 else "(restored)")
        res = svc.results.get(rid)
        shown = ("cancelled" if res is None
                 else f"optimum={res.optimum}" if res.status == "done"
                 else f"{res.status} anytime={res.optimum}")
        span = (f"rounds={ticket.submitted_round}..{ticket.finished_round} "
                f"latency={ticket.finished_round - ticket.submitted_round}"
                if ticket is not None and ticket.finished_round is not None
                else f"rounds=..{res.retired_round}" if res is not None
                else "")
        print(f"  rid={rid:3d} {label} {shown} {span}")
    done = sum(1 for r in svc.results.values() if r.status == "done")
    print(f"drained {len(served)} requests ({done} exact) in "
          f"{svc.rounds} rounds, {wall:.2f}s -> "
          f"{done / max(wall, 1e-9):.2f} instances/s")
    if args.metrics:
        snap = svc.metrics()
        util = snap.value("lane_utilization")
        steals = snap.value("steal_received", scope="intra")
        print(f"metrics: nodes={snap.value('engine_nodes')} "
              f"dispatches={snap.value('engine_dispatches')} "
              f"util={util:.3f} steals intra={steals} "
              f"queue_depth={snap.value('service_queue_depth')}")
    if args.trace:
        print(f"trace -> {args.trace}")
    if args.ckpt:
        svc.save(args.ckpt)
        print(f"service checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
