"""Entry ``serve``: many tenants' requests over one lane pool, through
``Solver.serve`` (``SolverService.submit`` / ``step_round``).

Set-up builds the service and drains one request per slot (the pool's
first entries, the same every seed), which compiles the round and the
stack rebuild.  The window is one thread: it submits what is due (open
loop) or tops the queue up (backlog), then runs one ``step_round``, which
returns after its own readback; a request is answered when its result
appears after a round.

Open loop: every request due in the window is followed to its answer,
for at most ``drain_s`` after the window; latency runs from the due time,
so the wait behind a slow round counts.  Backlog: the window ends with
the first round that ends after ``--seconds`` and ``served_per_s`` counts
the requests answered in it; then every request submitted is followed to
its answer, for at most ``drain_s``, and judged.
"""

from __future__ import annotations

import time

import numpy as np

import harness
from gnp import to_graph
from judge import Answer

WARM_RID = 1 << 30          # request ids of the set-up requests
TRACE_SECONDS = 3           # the part of the window ``--trace 1`` records


def run(ctx: harness.Context) -> harness.Record:
    from repro.service import SolveRequest
    from repro.solver import Solver, SolverConfig

    cfg, stream = ctx.config, ctx.traffic
    svc = Solver(SolverConfig(lanes=cfg["lanes"],
                              steps_per_round=cfg["steps_per_round"],
                              mesh=ctx.mesh())).serve(
        max_n=cfg["max_n"], slots=cfg["slots"])
    for k, entry in enumerate(stream.pool[:cfg["slots"]]):
        svc.submit(SolveRequest(rid=WARM_RID + k, family=entry.family,
                                graph=to_graph(entry.dense, f"warm{k}")))
    svc.drain()
    def pool_count():
        return svc.rounds, int(np.asarray(svc.lanes.nodes,
                                          dtype=np.int64).sum())

    at_open = pool_count()
    seen = len(svc.results)
    ctx.setup_done()

    due = stream.due_times(ctx.seconds)
    requests, answered, submitted_at, step_s = {}, {}, {}, []
    tracer = ctx.tracer()

    def submit(j: int, now: float) -> None:
        with harness.span("harness.generate"):
            req = stream.request(j)
            graph = to_graph(req.dense, f"r{j}")
        with harness.span("service.submit"):
            svc.submit(SolveRequest(rid=j, graph=graph, family=req.family,
                                    node_budget=cfg.get("node_budget")))
        requests[j] = req
        submitted_at[j] = now

    def step(t0: float) -> float:
        nonlocal seen
        with harness.span("service.step_round"):
            s = time.perf_counter()
            svc.step_round()
            e = time.perf_counter()
        step_s.append((s - t0, e - s))
        rids = list(svc.results)
        for rid in rids[seen:]:
            answered[rid] = e - t0
        seen = len(rids)
        return e - t0

    t0 = time.perf_counter()
    tracer.start()
    j, open_at_close, at_close = 0, None, None
    if due is not None:                                   # open loop
        deadline = ctx.seconds + ctx.mix["drain_s"]
        while True:
            now = time.perf_counter() - t0
            if tracer.active and now >= TRACE_SECONDS:
                tracer.stop()
            if open_at_close is None and now >= ctx.seconds:
                open_at_close = j - len(answered)
                at_close = pool_count()
            while j < len(due) and due[j] <= now:
                submit(j, time.perf_counter() - t0)
                j += 1
            if len(answered) < j:
                if step(t0) > deadline:
                    break
            elif j < len(due):
                time.sleep(max(0.0, due[j] - (time.perf_counter() - t0)))
            else:
                break
        if at_close is None:                # all answered before the close
            open_at_close, at_close = 0, pool_count()
        window_s = ctx.seconds
        served = None
        counted = list(range(len(due)))
    else:                                                 # backlog
        while True:
            while len(svc.queue) < stream.depth:
                submit(j, time.perf_counter() - t0)
                j += 1
            end = step(t0)
            if tracer.active and end >= TRACE_SECONDS:
                tracer.stop()
            if end >= ctx.seconds:
                break
        window_s = end
        served = len(answered)
        at_close = pool_count()
        deadline = end + ctx.mix["drain_s"]
        while len(answered) < j and step(t0) <= deadline:
            pass
        counted = list(range(j))
    tracer.stop()
    window_rounds = at_close[0] - at_open[0]

    answers, latency = [], []
    for rid in counted:
        req = requests[rid]
        res = svc.results.get(rid) if rid in answered else None
        base = stream.pool[req.pool].dense
        if res is None:
            answers.append(Answer(req.pool, req.family, req.dense, base,
                                  "missing"))
            continue
        latency.append(answered[rid] - (due[rid] if due is not None
                                        else submitted_at[rid]))
        answers.append(Answer(req.pool, req.family, req.dense, base,
                              res.status, int(res.optimum),
                              np.asarray(res.payload)))
    e2e = {"served_per_s": None if served is None else served / window_s}
    if due is not None and latency:
        e2e["latency_p50_s"] = harness.percentile(latency, 50)
        e2e["latency_p95_s"] = harness.percentile(latency, 95)
    lines = [f"window: {len(counted)} requests judged, {len(answered)} "
             f"answered, {window_rounds} rounds; window {window_s:.3f} s"]
    if due is not None:
        late = np.array([submitted_at[r] - due[r] for r in counted])
        lines.append(f"generator late: mean {late.mean() * 1e3:.3f} ms, "
                     f"max {late.max() * 1e3:.3f} ms over {len(late)} "
                     f"requests; rate {len(due) / ctx.seconds:.3f}/s")
        lines.append(f"at the window's close: {open_at_close} requests "
                     f"open of {len(due)} due")
    in_window = [d for s, d in step_s if s < ctx.seconds]
    return harness.Record(
        e2e=e2e, answers=answers,
        host={"step_round_s": in_window, "nodes": at_close[1] - at_open[1],
              "rounds": window_rounds, "steps": cfg["steps_per_round"],
              "lanes_per_chip": cfg["lanes"], "chips": ctx.cell.chips},
        trace_path=tracer.path, lines=lines)
