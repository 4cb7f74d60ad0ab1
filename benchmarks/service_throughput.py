"""Service throughput: continuous batching vs sequential per-instance solves.

The headline claim of the solver service (DESIGN.md §Solver service): K
mixed instances multiplexed over ONE lane pool finish faster than K
dedicated ``solve`` calls run back-to-back with the same lane count.  Two
effects compound:

  * compilation amortization — the stacked tables are jit *arguments*, so
    the service compiles one round for the whole stream, while each
    sequential ``solve`` retraces its instance-specific closures;
  * tail packing — a draining instance's idle lanes are immediately
    retargeted to other tenants instead of spinning until the slowest
    lane finishes.

Writes ``BENCH_service.json`` at the repo root and a CSV artifact; every
optimum is asserted against the serial oracle before timing is reported.
Both legs run through the ``repro.solver.Solver`` facade (ISSUE 4), so
this benchmark doubles as the proof that the session layer adds no
measurable overhead over the pre-facade drivers.

``--backend`` selects the stacked shared-evaluate kernel (DESIGN.md §5.3):
``jnp`` (default), ``pallas`` or ``both``.  The Pallas leg runs the kernel
body in interpret mode on CPU, so its number is a correctness/regression
canary, not a speed claim; on TPU it is the compiled kernel.  The JSON is
merged on write, so recording one backend preserves the other's entry.

``--devices 1,2,4`` adds the mesh-sharding axis (DESIGN.md §9): the same
instance mix is drained by a service sharded over N forced host devices
(``--lanes`` stays PER DEVICE, so the total lane pool grows with N).  On
a CPU host the forced devices share the same cores, so wall-clock cannot
scale; the hardware-neutral scaling metric is ROUNDS-TO-DRAIN, which
falls as the lane pool widens.  The legs run in one subprocess (jax
locks the device count at first init); the 1-device leg is the plain
``jit`` path and must reproduce the in-process service leg's round count
exactly — the sharding infrastructure is proven overhead-free where it
is off.  Results merge-write under the ``device_axis`` key.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import ART_DIR, bench_meta, write_csv
from repro import registry
from repro.problems import gnp_graph, random_regularish_graph
from repro.service import SolveRequest
from repro.solver import Solver, SolverConfig

OUT = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_service.json"))

LANES = 32
SLOTS = 4
STEPS = 64


def instance_mix(quick: bool):
    """K = 8 mixed vc + ds instances of varied sizes (K = 4 quick)."""
    mix = [
        ("vc", gnp_graph(18, 0.30, seed=7)),
        ("ds", gnp_graph(14, 0.25, seed=2)),
        ("vc", random_regularish_graph(20, 4, seed=3)),
        ("ds", gnp_graph(12, 0.30, seed=9)),
        ("vc", gnp_graph(16, 0.35, seed=5)),
        ("ds", gnp_graph(13, 0.30, seed=4)),
        ("vc", gnp_graph(20, 0.25, seed=11)),
        ("ds", gnp_graph(15, 0.25, seed=6)),
    ]
    return mix[:4] if quick else mix


def oracle(family: str, graph) -> int:
    return Solver().oracle(registry.problem(family, graph)).best


def run_sequential(mix, oracles) -> float:
    """Timed region covers ONLY the solves (oracle checks run outside).

    Facade-driven (ISSUE 4): one Solver session, K sequential solves —
    the session layer must add no measurable overhead over the old
    ``core.distributed.solve`` loop it replaced.
    """
    solver = Solver(SolverConfig(lanes=LANES, steps_per_round=STEPS,
                                 bootstrap_rounds=2, bootstrap_steps=4))
    t0 = time.perf_counter()
    best = []
    for family, graph in mix:
        res = solver.solve(registry.problem(family, graph))
        best.append(res.stats.best)
    wall = time.perf_counter() - t0
    for (family, graph), got, want in zip(mix, best, oracles):
        assert got == want, (graph.name, got, want)
    return wall


def run_service(mix, oracles, backend: str = "jnp",
                trace_path: str = None, metrics: bool = False,
                mesh=None, lanes: int = LANES, steps: int = STEPS):
    """Drain the mix through one service; -> (wall_s, rounds_to_drain)."""
    max_n = max(g.n for _, g in mix)
    svc = Solver(SolverConfig(lanes=lanes, steps_per_round=steps,
                              backend=backend, trace_path=trace_path,
                              metrics=metrics, mesh=mesh)).serve(
        max_n=max_n, slots=SLOTS)
    reqs = [SolveRequest(rid=i, graph=g, family=fam)
            for i, (fam, g) in enumerate(mix)]
    t0 = time.perf_counter()
    for r in reqs:
        svc.submit(r)
    results = svc.drain()
    wall = time.perf_counter() - t0
    for i, ((family, graph), want) in enumerate(zip(mix, oracles)):
        assert results[i].optimum == want, (graph.name, results[i].optimum)
    return wall, svc.rounds


def run(quick: bool = False, backend: str = "jnp") -> dict:
    backends = ("jnp", "pallas") if backend == "both" else (backend,)
    mix = instance_mix(quick)
    k = len(mix)
    oracles = [oracle(fam, g) for fam, g in mix]
    seq = run_sequential(mix, oracles)
    out = {
        "workload": [f"{fam}:{g.name}" for fam, g in mix],
        "k_instances": k,
        "lanes": LANES,
        "slots": SLOTS,
        "steps_per_round": STEPS,
        "unit": "instances / second (CPU; end-to-end incl. compilation; "
                "pallas = interpret-mode kernel, a correctness canary)",
        "sequential": {"wall_s": round(seq, 3),
                       "instances_per_sec": round(k / seq, 3)},
    }
    for b in backends:
        svc, svc_rounds = run_service(mix, oracles, backend=b)
        key = "service" if b == "jnp" else f"service_{b}"
        out[key] = {"wall_s": round(svc, 3),
                    "instances_per_sec": round(k / svc, 3),
                    "rounds": svc_rounds}
        out["speedup" if b == "jnp" else f"speedup_{b}"] = round(seq / svc, 2)
        if b == "jnp":
            # Telemetry-overhead leg (DESIGN.md §8): same drain with the
            # metrics registry + JSONL trace on — the acceptance bar is
            # < 5% regression over the plain service leg.  The trace
            # doubles as the standard report artifact for this suite
            # (tools/trace_report.py, wired by benchmarks/run.py).
            trace_dir = os.path.join(ART_DIR, "traces")
            os.makedirs(trace_dir, exist_ok=True)
            trace_path = os.path.join(trace_dir, "service_throughput.jsonl")
            tele, _ = run_service(mix, oracles, backend=b,
                                  trace_path=trace_path, metrics=True)
            out["service_telemetry"] = {
                "wall_s": round(tele, 3),
                "instances_per_sec": round(k / tele, 3),
                "overhead_vs_service": round(tele / svc - 1.0, 4),
                "trace": os.path.relpath(trace_path,
                                         os.path.dirname(ART_DIR)),
            }
    out["meta"] = bench_meta()
    return out


# -- mesh device axis (DESIGN.md §9) -----------------------------------------

#: Axis legs run a deliberately SMALL per-device pool: with 8 lanes x 8
#: steps the 1-device drain takes many rounds and lane-pool width is the
#: binding resource, so adding devices (lanes stay per-device) must cut
#: rounds-to-drain.  The main LANES x STEPS config drains the mix in a
#: couple of rounds — no scaling headroom to measure there.
AX_LANES = 8
AX_STEPS = 8


def _device_legs(devices, quick: bool) -> dict:
    """Every device leg on ``jax.devices()[:d]``, in this process.

    All legs share one process and one mix.  A ``pre_shard`` leg at the
    MAIN config with mesh=None (the plain jit path) is emitted alongside:
    it is the identical deterministic computation to the in-process
    service leg and gates on it.
    """
    import jax
    mix = instance_mix(quick)
    oracles = [oracle(fam, g) for fam, g in mix]
    k = len(mix)
    wall0, rounds0 = run_service(mix, oracles)
    legs = {}
    for d in devices:
        assert d <= len(jax.devices()), (d, jax.devices())
        mesh = (jax.make_mesh((d,), ("workers",),
                              devices=jax.devices()[:d])
                if d > 1 else None)
        wall, rounds = run_service(mix, oracles, mesh=mesh,
                                   lanes=AX_LANES, steps=AX_STEPS)
        legs[str(d)] = {"devices": d, "lanes_per_device": AX_LANES,
                        "total_lanes": AX_LANES * d, "rounds": rounds,
                        "wall_s": round(wall, 3),
                        "instances_per_sec": round(k / wall, 3)}
    return {"pre_shard": {"rounds": rounds0, "wall_s": round(wall0, 3)},
            "legs": legs}


def _axis_child(devices, quick: bool) -> None:
    """Subprocess body on the CPU platform: the parent sets XLA_FLAGS to
    force host devices before spawning us (jax fixes the device count at
    first init)."""
    print("DEVICES_RESULT " + json.dumps(_device_legs(devices, quick)))


def run_devices(devices, quick: bool, baseline: dict = None) -> dict:
    """Run the device legs, check scaling, -> merged section.

    Where this process already sees enough devices (a chip host, or a CPU
    started with forced host devices) the legs run in-process on
    ``jax.devices()[:d]``: a parent that holds a chip cannot hand it to a
    child.  Only on the CPU platform does it spawn a child with forced
    host devices.

    Scaling is asserted on rounds-to-drain (forced host devices share the
    same CPU cores, so wall-clock is context, not a claim): every d > 1
    leg must drain the mix in FEWER rounds than the 1-device leg.  The
    1-device leg is additionally pinned to the in-process service leg's
    round count — same deterministic computation, so sharding-off must be
    exactly the pre-shard service.
    """
    import jax

    devices = sorted(set(devices))
    if len(jax.devices()) >= max(devices):
        res = _device_legs(devices, quick)
    elif jax.default_backend() == "cpu":
        env = dict(os.environ)
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{max(devices + [2])}")
        cmd = [sys.executable, "-m", "benchmarks.service_throughput",
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
        res = json.loads(line[len("DEVICES_RESULT "):])
    else:
        raise SystemExit(f"--devices {max(devices)} needs that many devices;"
                         f" {jax.default_backend()} has {len(jax.devices())}")
    legs, pre = res["legs"], res["pre_shard"]
    axis = {
        "unit": "rounds-to-drain (hardware-neutral; forced host devices "
                "share CPU cores, wall_s is context only)",
        "lanes_per_device": AX_LANES, "steps_per_round": AX_STEPS,
        "slots": SLOTS,
        "pre_shard": pre,
        "legs": legs,
        "meta": bench_meta(),
    }
    if baseline is not None:
        # Pre-shard noise gate: mesh=None at the main config is the plain
        # jit path — the identical deterministic search, so the round
        # count must REPRODUCE the in-process service leg exactly; the
        # wall band is lenient (fresh-process compile, shared cores).
        assert pre["rounds"] == baseline["rounds"], (
            "pre-shard leg diverged from the in-process service leg",
            pre, baseline)
        assert pre["wall_s"] < 3.0 * baseline["wall_s"] + 1.0, (
            "pre-shard leg wall-clock outside the noise band",
            pre, baseline)
        axis["pre_shard_matches_service"] = True
    if "1" in legs:
        base = legs["1"]
        for d in devices:
            leg = legs[str(d)]
            leg["scaling_rounds"] = round(base["rounds"] / leg["rounds"], 2)
            if d > 1:
                assert leg["rounds"] < base["rounds"], (
                    "no rounds-to-drain scaling", d, legs)
    return axis


def main(quick: bool = False, backend: str = "jnp",
         devices=None) -> None:
    out = run(quick, backend)
    if devices:
        out["device_axis"] = run_devices(list(devices), quick,
                                         baseline=out.get("service"))
    modes = [m for m in ("sequential", "service", "service_telemetry",
                         "service_pallas") if m in out]
    rows = [{"mode": m, "wall_s": out[m]["wall_s"],
             "instances_per_sec": out[m]["instances_per_sec"]}
            for m in modes]
    path = write_csv("service_throughput.csv", rows,
                     ["mode", "wall_s", "instances_per_sec"])
    print(json.dumps(out, indent=1))
    if not quick:
        # Merge-write so recording one backend keeps the other's service
        # entry.  Retained speedups are recomputed against THIS run's
        # sequential baseline (the merged file must stay internally
        # consistent: speedup_* == sequential.wall_s / service_*.wall_s);
        # a retained entry whose wall time came from a different machine
        # is still the previous run's measurement, only its ratio moves.
        merged = {}
        if os.path.exists(OUT):
            try:
                with open(OUT) as f:
                    merged = json.load(f)
            except ValueError:
                merged = {}
        merged.update(out)
        seq_wall = merged["sequential"]["wall_s"]
        for svc_key, sp_key in (("service", "speedup"),
                                ("service_pallas", "speedup_pallas")):
            if svc_key in merged:
                merged[sp_key] = round(seq_wall / merged[svc_key]["wall_s"],
                                       2)
        with open(OUT, "w") as f:
            json.dump(merged, f, indent=1)
            f.write("\n")
        print(f"service -> {OUT}")
    print(f"service -> {path}")


def cli(argv=None) -> None:
    """Module CLI; also the pass-through target for
    ``python -m benchmarks.run --only service -- <args>``."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--backend", choices=["jnp", "pallas", "both"],
                    default="jnp",
                    help="stacked shared-evaluate kernel backend(s) to "
                         "measure (DESIGN.md §5.3)")
    ap.add_argument("--devices", default=None,
                    help="comma list of device counts for the mesh "
                         "sharding axis, e.g. 1,2,4 (DESIGN.md §9; runs "
                         "in a subprocess with forced host devices)")
    ap.add_argument("--_axis-child", dest="axis_child", default=None,
                    help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if a.axis_child:
        _axis_child([int(x) for x in a.axis_child.split(",")], a.quick)
        return
    devices = ([int(x) for x in a.devices.split(",")]
               if a.devices else None)
    main(a.quick, a.backend, devices=devices)


if __name__ == "__main__":
    cli()
