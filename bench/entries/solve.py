"""Entry ``solve``: one hard instance at a time through ``Solver.solve``.

Set-up builds the solver and compiles each panel instance's round (one
round of each, ``max_rounds=1``; the adjacency is a constant of the round,
so every instance is a program of its own).  The window solves the panel
whole, pass after pass, until ``--seconds`` have passed at the end of a
pass; every solve in it is timed and judged.

End to end: ``solve_s`` = the window's time over the solves in it.
Host readings for the readers: per solve its rounds, nodes and lanes, and
which solves the profiler recorded.
"""

from __future__ import annotations

import time

import numpy as np

import harness
from gnp import to_graph
from judge import Answer

TRACE_SOLVES = 1            # solves a ``--trace 1`` run records


def run(ctx: harness.Context) -> harness.Record:
    from repro import registry
    from repro.solver import Solver, SolverConfig

    cfg, panel = ctx.config, ctx.traffic
    handles = [registry.problem(cfg["problem"], to_graph(d, name))
               for d, name in zip(panel.dense, panel.names)]
    settings = dict(lanes=cfg["lanes"], steps_per_round=cfg["steps_per_round"],
                    mesh=ctx.mesh())
    warm = Solver(SolverConfig(max_rounds=1, **settings))
    for h in handles:
        warm.solve(h)
    solver = Solver(SolverConfig(max_rounds=cfg["max_rounds"], **settings))
    ctx.setup_done()

    tracer = ctx.tracer()
    solves = []
    t0 = time.perf_counter()
    tracer.start()
    pass_no = 0
    while True:
        for i in panel.order(pass_no):
            traced = tracer.active
            with harness.span("solve"):
                t = time.perf_counter()
                res = solver.solve(handles[i])
                t = time.perf_counter() - t
            open_lanes = int(np.asarray(res.lanes.active).sum())
            st = res.stats
            solves.append(dict(i=int(i), wall=t, best=st.best, rounds=st.rounds,
                               nodes=st.nodes, lanes=st.lanes,
                               open_lanes=open_lanes, traced=traced,
                               payload=np.asarray(res.payload)))
            if tracer.active and len(solves) >= TRACE_SOLVES:
                tracer.stop()
        pass_no += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    window_s = time.perf_counter() - t0
    tracer.stop()

    answers = [Answer(key=s["i"], family=cfg["problem"],
                      dense=panel.dense[s["i"]], base=panel.dense[s["i"]],
                      status="done" if s["open_lanes"] == 0 else "unproven",
                      value=s["best"], payload=s["payload"])
               for s in solves]
    lines = [f"window: {len(solves)} solves in {window_s:.3f} s "
             f"({pass_no} passes); per instance rounds "
             f"{sorted({(s['i'], s['rounds']) for s in solves})}",
             "solve seconds in order: " + " ".join(
                 f"{s['i']}:{s['wall']:.4f}" for s in solves)]
    return harness.Record(
        e2e={"solve_s": window_s / len(solves)}, answers=answers,
        host={"solves": solves, "steps": cfg["steps_per_round"],
              "lanes_per_chip": cfg["lanes"]},
        trace_path=tracer.path, lines=lines)
