"""Request latency under load: scheduling policies on a Poisson trace.

The ticketed service (DESIGN.md §7) exists so a deployment can express
request lifecycles — priorities, deadlines, eviction — instead of batch
drains.  This benchmark measures what that buys: a Poisson arrival trace
of mixed HARD (large, minutes-of-rounds) and EASY (small,
latency-sensitive, deadline-carrying) requests is replayed against the
same service under each scheduling policy (``fifo`` — the pre-ticket
baseline, ``priority``, ``sjf``), and we record per-request latency
(submission → resolution, in service rounds and wall seconds) and the
deadline-hit rate.

The claim under test: with slots scarce, FIFO lets early-arriving hard
requests head-of-line-block the easy deadline traffic into expiry, while
priority scheduling admits the easy requests first and meets their
deadlines — priority must be >= fifo on deadline-hit rate (asserted).

Writes ``BENCH_service.json`` (merge-write, key ``latency``) and a CSV
artifact; every DONE optimum is asserted against the serial oracle.  The
trace is deterministic (seeded) so latencies in rounds are reproducible;
wall-clock numbers are environment-dependent context.

``--devices 1,2,4`` adds the mesh-sharding axis (DESIGN.md §9): the same
trace replays under the ``priority`` policy with the lane pool sharded
over N forced host devices (``LANES`` is per device).  Wider pools drain
the hard head-of-line jobs in fewer rounds, so total rounds and the
latency percentiles (in rounds — the hardware-neutral unit) must fall;
the legs run in one subprocess, same pattern as service_throughput.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import bench_meta, write_csv
from repro import registry
from repro.problems import gnp_graph
from repro.service import SolveRequest, TicketStatus
from repro.solver import Solver, SolverConfig

OUT = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_service.json"))

LANES = 16
SLOTS = 2
STEPS = 4
POLICIES = ("fifo", "priority", "sjf")
EASY_PRIORITY = 5
EASY_DEADLINE = 80            # rounds from submission
MEAN_GAP = 2.0                # Poisson arrivals: mean inter-arrival rounds


def poisson_trace(quick: bool):
    """[(arrival_round, SolveRequest)] — hard requests front-loaded, easy
    deadline-carrying requests arriving into the resulting contention.

    Hard jobs are dominating set on SPARSE graphs (weak coverage bound →
    thousands of search nodes, hundreds of service rounds); one of the
    early hard jobs is medium-sized so a slot frees inside the easy
    requests' deadline window — that freed slot is exactly where the
    scheduling policy decides who lives: FIFO hands it to the next queued
    hard job, priority/sjf to the deadline traffic.
    """
    if quick:
        hard = [("ds", gnp_graph(24, 0.12, seed=100)),
                ("ds", gnp_graph(20, 0.20, seed=101))]
        n_easy = 3
    else:
        hard = [("ds", gnp_graph(30, 0.10, seed=100)),   # long
                ("ds", gnp_graph(22, 0.15, seed=101)),   # medium: frees slot
                ("ds", gnp_graph(30, 0.10, seed=102)),   # long
                ("ds", gnp_graph(28, 0.10, seed=103))]   # long
        n_easy = 6
    easy = [("vc" if i % 2 else "ds", gnp_graph(12 + i % 3, 0.30, seed=i))
            for i in range(n_easy)]
    rng = np.random.default_rng(7)
    gaps = rng.exponential(scale=MEAN_GAP, size=len(hard) + n_easy)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    trace = []
    for i, (fam, g) in enumerate(hard + easy):
        is_easy = i >= len(hard)
        trace.append((int(arrivals[i]), SolveRequest(
            rid=i, graph=g, family=fam,
            priority=EASY_PRIORITY if is_easy else 0,
            deadline_rounds=EASY_DEADLINE if is_easy else None)))
    return trace


def replay(trace, scheduler: str, oracles, mesh=None) -> dict:
    svc = Solver(SolverConfig(lanes=LANES, steps_per_round=STEPS,
                              scheduler=scheduler, mesh=mesh)).serve(
        max_n=max(r.graph.n for _, r in trace), slots=SLOTS)
    pending = sorted(trace, key=lambda a: a[0])
    tickets, t_submit, t_finish = {}, {}, {}
    while pending or svc._has_work():
        while pending and pending[0][0] <= svc.rounds:
            _, req = pending.pop(0)
            tickets[req.rid] = svc.submit(req)
            t_submit[req.rid] = time.perf_counter()
        svc.step_round()
        for rid, t in tickets.items():
            if rid not in t_finish and t.done():
                t_finish[rid] = time.perf_counter()

    lat_rounds, lat_wall, with_deadline, hits = [], [], 0, 0
    for arrival, req in trace:
        t = tickets[req.rid]
        if t.status is TicketStatus.DONE:
            assert svc.results[req.rid].optimum == oracles[req.rid], req.rid
            lat_rounds.append(t.finished_round - arrival)
            lat_wall.append(t_finish[req.rid] - t_submit[req.rid])
        if req.deadline_rounds is not None:
            with_deadline += 1
            hits += t.status is TicketStatus.DONE
    pct = (lambda xs, q: round(float(np.percentile(xs, q)), 3)
           if xs else None)
    return {
        "completed": len(lat_rounds),
        "expired": sum(t.status is TicketStatus.EXPIRED
                       for t in tickets.values()),
        "p50_latency_rounds": pct(lat_rounds, 50),
        "p95_latency_rounds": pct(lat_rounds, 95),
        "p50_latency_s": pct(lat_wall, 50),
        "p95_latency_s": pct(lat_wall, 95),
        "deadline_hit_rate": round(hits / with_deadline, 3),
        "total_rounds": svc.rounds,
    }


def run(quick: bool = False) -> dict:
    trace = poisson_trace(quick)
    oracles = {r.rid: Solver().oracle(registry.problem(r.family,
                                                       r.graph)).best
               for _, r in trace}
    n_deadline = sum(r.deadline_rounds is not None for _, r in trace)
    out = {
        "workload": {
            "requests": len(trace),
            "with_deadline": n_deadline,
            "deadline_rounds": EASY_DEADLINE,
            "mean_arrival_gap_rounds": MEAN_GAP,
            "lanes": LANES, "slots": SLOTS, "steps_per_round": STEPS,
        },
        "unit": "request latency submission->resolution (service rounds; "
                "wall seconds are CPU context)",
    }
    for policy in POLICIES:
        out[policy] = replay(trace, policy, oracles)
    # The headline claim: priority scheduling keeps deadline traffic alive
    # that FIFO head-of-line-blocks into expiry.
    assert out["priority"]["deadline_hit_rate"] >= \
        out["fifo"]["deadline_hit_rate"], out
    return out


# -- mesh device axis (DESIGN.md §9) -----------------------------------------

def _device_legs(devices, quick: bool) -> dict:
    """Replay the trace per device count (priority policy) on
    ``jax.devices()[:d]``, in this process."""
    import jax
    trace = poisson_trace(quick)
    oracles = {r.rid: Solver().oracle(registry.problem(r.family,
                                                       r.graph)).best
               for _, r in trace}
    legs = {}
    for d in devices:
        assert d <= len(jax.devices()), (d, jax.devices())
        mesh = (jax.make_mesh((d,), ("workers",),
                              devices=jax.devices()[:d])
                if d > 1 else None)
        rep = replay(trace, "priority", oracles, mesh=mesh)
        legs[str(d)] = {"devices": d, "lanes_per_device": LANES,
                        "total_lanes": LANES * d,
                        "total_rounds": rep["total_rounds"],
                        "p50_latency_rounds": rep["p50_latency_rounds"],
                        "p95_latency_rounds": rep["p95_latency_rounds"],
                        "deadline_hit_rate": rep["deadline_hit_rate"],
                        "completed": rep["completed"]}
    return legs


def _axis_child(devices, quick: bool) -> None:
    """Subprocess body on the CPU platform: the parent forced the host
    device count before spawning us."""
    print("DEVICES_RESULT " + json.dumps(_device_legs(devices, quick)))


def run_devices(devices, quick: bool) -> dict:
    """Run the device legs; scaling asserted on the total rounds-to-drain
    of the priority replay (latency percentiles are recorded context —
    the easy traffic is already near the floor).

    Where this process already sees enough devices the legs run
    in-process on ``jax.devices()[:d]`` (a parent holding a chip cannot
    hand it to a child); only on the CPU platform does it spawn a child
    with forced host devices.
    """
    import jax

    devices = sorted(set(devices))
    if len(jax.devices()) >= max(devices):
        legs = _device_legs(devices, quick)
    elif jax.default_backend() == "cpu":
        env = dict(os.environ)
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{max(devices + [2])}")
        cmd = [sys.executable, "-m", "benchmarks.service_latency",
               "--_axis-child", ",".join(str(d) for d in devices)]
        if quick:
            cmd.append("--quick")
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=3600,
                              cwd=os.path.dirname(os.path.dirname(
                                  os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr[-4000:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("DEVICES_RESULT ")][-1]
        legs = json.loads(line[len("DEVICES_RESULT "):])
    else:
        raise SystemExit(f"--devices {max(devices)} needs that many devices;"
                         f" {jax.default_backend()} has {len(jax.devices())}")
    axis = {
        "unit": "priority-policy replay; rounds are hardware-neutral, "
                "wider pools must drain in fewer total rounds",
        "policy": "priority", "lanes_per_device": LANES,
        "legs": legs,
        "meta": bench_meta(),
    }
    if "1" in legs:
        base = legs["1"]
        for d in devices:
            leg = legs[str(d)]
            leg["scaling_rounds"] = round(
                base["total_rounds"] / leg["total_rounds"], 2)
            if d > 1:
                assert leg["total_rounds"] < base["total_rounds"], (
                    "no rounds-to-drain scaling", d, legs)
                assert leg["deadline_hit_rate"] >= \
                    base["deadline_hit_rate"], (d, legs)
    return axis


def main(quick: bool = False, devices=None) -> None:
    out = run(quick)
    if devices:
        out["device_axis"] = run_devices(list(devices), quick)
    rows = [{"policy": p, **{k: v for k, v in out[p].items()}}
            for p in POLICIES]
    path = write_csv("service_latency.csv", rows,
                     ["policy"] + [k for k in rows[0] if k != "policy"])
    print(json.dumps(out, indent=1))
    if not quick:
        merged = {}
        if os.path.exists(OUT):
            try:
                with open(OUT) as f:
                    merged = json.load(f)
            except ValueError:
                merged = {}
        out["meta"] = bench_meta()
        merged["latency"] = out
        with open(OUT, "w") as f:
            json.dump(merged, f, indent=1)
            f.write("\n")
        print(f"service latency -> {OUT}")
    print(f"service latency -> {path}")


def cli(argv=None) -> None:
    """Module CLI; also the pass-through target for
    ``python -m benchmarks.run --only latency -- <args>``."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--devices", default=None,
                    help="comma list of device counts for the mesh "
                         "sharding axis, e.g. 1,2,4 (DESIGN.md §9)")
    ap.add_argument("--_axis-child", dest="axis_child", default=None,
                    help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if a.axis_child:
        _axis_child([int(x) for x in a.axis_child.split(",")], a.quick)
        return
    main(a.quick, devices=[int(x) for x in a.devices.split(",")]
         if a.devices else None)


if __name__ == "__main__":
    cli()
