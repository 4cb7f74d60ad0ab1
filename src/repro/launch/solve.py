"""Solver launcher: the paper's framework as a CLI (registry-driven).

  PYTHONPATH=src python -m repro.launch.solve --problem vc \
      --instance reg:48:4:1 --lanes 32 [--ckpt run.ckpt] [--resume]

``--problem`` accepts any family registered with
``repro.registry.register_problem`` and ``--instance`` uses that family's
own registered parser (graph families: ``gnp:<n>:<p*100>:<seed>``,
``reg:<n>:<k>:<seed>``, ``cell60``; subset sum: ``ss:<n>:<seed>``).  The
CLI contains zero per-problem branching: parsing, capability validation
and construction all come from the registry, and the solve itself runs
through the :class:`repro.solver.Solver` facade (DESIGN.md §6).
"""

from __future__ import annotations

import argparse
import time

from repro import compile_cache, registry
from repro.problems.graphs import parse_graph_instance
from repro.solver import Solver, SolverConfig


def parse_instance(spec: str):
    """DEPRECATED graph-spec parser, kept for pre-registry callers — use
    ``repro.registry.get(family).parse`` (each family owns its grammar)."""
    return parse_graph_instance(spec)


def main() -> None:
    families = registry.names()
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", choices=sorted(families), default="vc",
                    help="registered problem family: " + "; ".join(
                        f"{n}: {registry.get(n).doc}" for n in families))
    ap.add_argument("--backend", default="jnp",
                    help="node-evaluation kernel backend (validated against "
                         "the family's registered capabilities)")
    ap.add_argument("--instance", default="reg:48:4:1")
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--steps-per-round", type=int, default=64)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a JSONL search trace (repro.obs schema; "
                         "summarize with tools/trace_report.py)")
    ap.add_argument("--metrics", action="store_true",
                    help="collect in-process metrics and print a summary")
    args = ap.parse_args()
    compile_cache.enable()

    # Capability check is registry data, not per-problem branching
    # (DESIGN.md §5.4/§6): a problem gains --backend pallas the moment its
    # registration does.
    spec = registry.get(args.problem)
    if args.backend not in spec.backends:
        ap.error(
            f"--backend {args.backend} is not supported by --problem "
            f"{args.problem} (registry advertises: "
            f"{', '.join(spec.backends)})")
    try:
        instance = spec.parse(args.instance)
    except ValueError as e:
        ap.error(str(e))

    config = SolverConfig(
        lanes=args.lanes, steps_per_round=args.steps_per_round,
        backend=args.backend, bootstrap_rounds=4, bootstrap_steps=8,
        checkpoint_every=args.ckpt_every if args.ckpt else 0,
        checkpoint_path=args.ckpt,
        resume_from=args.ckpt if args.resume else None,
        trace_path=args.trace, metrics=args.metrics)
    handle = registry.problem(args.problem, instance)
    print(f"{args.problem}[{spec.label(instance)}]: lanes={args.lanes} "
          f"backend={args.backend}")
    t0 = time.time()
    solver = Solver(config)
    result = solver.solve(handle)
    stats = result.stats
    print(f"optimum={stats.best} rounds={stats.rounds} nodes={stats.nodes} "
          f"T_S={stats.t_s} T_R={stats.t_r} wall={time.time()-t0:.1f}s")
    if args.metrics:
        snap = solver.metrics()
        util = snap.value("lane_utilization")
        steals = snap.value("steal_received", scope="intra")
        cross = snap.value("steal_received", scope="cross")
        print(f"metrics: nodes={snap.value('engine_nodes')} "
              f"dispatches={snap.value('engine_dispatches')} "
              f"util={util:.3f} steals intra={steals} cross={cross}")
    if args.trace:
        print(f"trace -> {args.trace}")


if __name__ == "__main__":
    main()
