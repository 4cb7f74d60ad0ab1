"""On-chip smoke test: the solver's main path, end to end, on a TPU.

  python chip_smoke.py             # one chip: phases (a)-(d), jnp and pallas
  python chip_smoke.py --chips 4   # four chips: the mesh phase only

One process, no children: it drives ``Solver.solve`` and ``Solver.serve``
(the ``SolverService``) through their public entry points, on both
node-evaluation backends, and checks every result against the serial
oracle (``Solver.oracle``) or a known optimum:

  (a) vc ``cell60`` — the paper's 60-cell analogue, n = 300, bipartite and
      4-regular, so the minimum vertex cover is exactly 150;
  (b) vc ``gnp:100:10:1`` — a real search (hundreds of thousands of nodes);
  (c) ds ``gnp:58:10:1`` — a dominating-set search the oracle finishes in
      well under a minute;
  (d) the service: 8 mixed vc/ds requests over 4 slots, ``cell60`` among
      them so the stacked tables are padded to n = 300.

Under ``jnp`` and ``pallas`` every phase must give the identical optimum,
node count, T_S and T_R, the optimum must equal the oracle's, and the
compiled ``pallas`` round must contain the Mosaic kernel
(``tpu_custom_call``).  ``--chips 4`` runs (b) on a 4-device mesh against
the one-chip solve and the oracle, and the sharded service on (d)'s
requests against the one-chip service, and prints how many lane rows each
device holds.

Per-phase compile seconds, wall seconds and ``peak_bytes_in_use`` are
printed as context; they are not benchmark metrics.  The last line of
standard output is one JSON object naming the device.  Without a TPU, or
without the repository's ``src/`` next to this file, the script exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

LANES = 1024                      # lanes per solve on one chip
STEPS = 64                        # engine steps per round
SLOTS = 4                         # service instance slots
CELL60_OPTIMUM = 150              # bipartite 4-regular, n = 300: n / 2
BACKENDS = ("jnp", "pallas")
KERNEL_MARK = "tpu_custom_call"

CELL = ("vc", "cell60")
VC_SEARCH = ("vc", "gnp:100:10:1")
DS_SEARCH = ("ds", "gnp:58:10:1")
SERVICE_MIX = (
    ("vc", "cell60"), ("ds", "gnp:50:12:1"), ("vc", "gnp:40:15:1"),
    ("ds", "gnp:48:10:1"), ("vc", "reg:48:4:1"), ("ds", "gnp:45:12:1"),
    ("vc", "gnp:60:8:2"), ("ds", "gnp:40:12:1"),
)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or inconsistent result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Clock:
    """Compile seconds (JAX's own compile events), wall seconds and the
    device's peak bytes, read around each phase."""

    def __init__(self, jax_mod, devices):
        self.compile_s = 0.0
        self.devices = devices
        jax_mod.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.compile_s += duration

    def peak_bytes(self) -> int:
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    def run(self, label: str, fn):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        print(f"  [{label}] compile_s={self.compile_s - c0:.2f} "
              f"wall_s={wall:.2f} peak_bytes_in_use={self.peak_bytes()}",
              flush=True)
        return out


def counts(stats) -> dict:
    return {"optimum": int(stats.best), "nodes": int(stats.nodes),
            "t_s": int(stats.t_s), "t_r": int(stats.t_r),
            "rounds": int(stats.rounds)}


def oracle_optimum(env, fam: str, spec: str) -> int:
    if (fam, spec) == CELL:
        return CELL60_OPTIMUM
    return env.Solver().oracle(env.registry.problem(fam, spec)).best


def assert_kernel_in_round(env, problem, lanes) -> None:
    """The compiled pallas round must hold the Mosaic kernel — proof that
    no interpreter or jnp path stood in for it on the device."""
    text = env.jax.jit(env.make_round(problem, STEPS)).lower(
        lanes).compile().as_text()
    check(KERNEL_MARK in text,
          f"compiled pallas round of {problem.name} has no {KERNEL_MARK}")


def solve_phase(env, clock, label, fam, spec, *, lanes, mesh=None,
                backends=BACKENDS, want=None):
    """Solve one instance under each backend; results must agree with
    each other and with ``want`` (the oracle's optimum)."""
    handle = env.registry.problem(fam, spec)
    got = {}
    for backend in backends:
        cfg = env.SolverConfig(lanes=lanes, steps_per_round=STEPS,
                               backend=backend, mesh=mesh)
        res = clock.run(f"{label} {backend}",
                        lambda: env.Solver(cfg).solve(handle))
        c = counts(res.stats)
        print(f"  {label} {fam}[{spec}] backend={backend} "
              f"lanes={res.stats.lanes} optimum={c['optimum']} "
              f"nodes={c['nodes']} T_S={c['t_s']} T_R={c['t_r']} "
              f"rounds={c['rounds']}", flush=True)
        check(c["t_s"] <= c["t_r"] + 1,
              f"{label} {backend}: T_S={c['t_s']} > T_R+1={c['t_r'] + 1}")
        if want is not None:
            check(c["optimum"] == want,
                  f"{label} {backend}: optimum {c['optimum']} != {want}")
        if backend == "pallas" and mesh is None:
            prob = handle.build(backend="pallas")
            assert_kernel_in_round(env, prob, env.init_lanes(prob, lanes))
        got[backend] = (c, res)
    if len(backends) > 1:
        ref = {k: v for k, v in got[backends[0]][0].items()}
        for backend in backends[1:]:
            check(got[backend][0] == ref,
                  f"{label}: {backend} {got[backend][0]} != "
                  f"{backends[0]} {ref}")
    return got


def service_phase(env, clock, label, mix, *, lanes, mesh=None,
                  backends=BACKENDS, want=None):
    """Serve ``mix`` under each backend: every ticket must reach DONE with
    the oracle's optimum, and the backends must agree on every count."""
    graphs = [(fam, env.registry.get(fam).parse(spec)) for fam, spec in mix]
    max_n = max(env.registry.get(fam).size(g) for fam, g in graphs)
    got = {}
    for backend in backends:
        cfg = env.SolverConfig(lanes=lanes, steps_per_round=STEPS,
                               backend=backend, mesh=mesh)

        def serve():
            svc = env.Solver(cfg).serve(max_n=max_n, slots=SLOTS)
            tickets = [svc.submit(env.SolveRequest(rid=i, graph=g,
                                                   family=fam))
                       for i, (fam, g) in enumerate(graphs)]
            svc.drain()
            return svc, tickets

        svc, tickets = clock.run(f"{label} {backend}", serve)
        results = {t.rid: (t.status.value, svc.results[t.rid].optimum)
                   for t in tickets}
        lanes_host = env.jax.device_get(svc.lanes)
        c = {"results": results, "rounds": svc.rounds,
             "nodes": int(lanes_host.nodes.sum()),
             "t_s": int(lanes_host.t_s.sum()),
             "t_r": int(lanes_host.t_r.sum())}
        print(f"  {label} backend={backend} lanes={svc.num_lanes} "
              f"slots={svc.spec.k} padded_n={svc.spec.n} "
              f"rounds={c['rounds']} nodes={c['nodes']} T_S={c['t_s']} "
              f"T_R={c['t_r']} results={results}", flush=True)
        for rid, (status, opt) in results.items():
            check(status == "done", f"{label} {backend}: rid {rid} {status}")
            if want is not None:
                check(opt == want[rid],
                      f"{label} {backend}: rid {rid} optimum {opt} != "
                      f"oracle {want[rid]}")
        # Each admission seeds a root without a request, so the service
        # bound is T_S <= T_R + admissions.
        check(c["t_s"] <= c["t_r"] + len(mix),
              f"{label} {backend}: T_S={c['t_s']} > T_R+{len(mix)}")
        if backend == "pallas" and mesh is None:
            spec = env.StackedSpec(n=max_n, k=SLOTS)
            tables = env.StackedTables(*(env.jax.numpy.asarray(t)
                                         for t in spec.empty_tables()))
            prob = spec.bind(tables, "pallas")
            assert_kernel_in_round(
                env, prob, env.init_lanes(prob, lanes, seed_root=False,
                                          bind_instance=False))
        got[backend] = (c, svc)
    if len(backends) > 1:
        ref = got[backends[0]][0]
        for backend in backends[1:]:
            check(got[backend][0] == ref,
                  f"{label}: {backend} {got[backend][0]} != "
                  f"{backends[0]} {ref}")
    return got


def lane_rows(arr) -> list:
    """(device id, lane rows) for each shard of a lane-sharded array."""
    return sorted((s.device.id, int(s.data.shape[0]))
                  for s in arr.addressable_shards)


def one_chip(env, clock) -> None:
    print(f"phase (a): {' '.join(CELL)}, optimum must be {CELL60_OPTIMUM}",
          flush=True)
    solve_phase(env, clock, "a", *CELL, lanes=LANES, want=CELL60_OPTIMUM)

    print(f"phase (b): {' '.join(VC_SEARCH)} against the serial oracle",
          flush=True)
    want_b = clock.run("b oracle", lambda: oracle_optimum(env, *VC_SEARCH))
    solve_phase(env, clock, "b", *VC_SEARCH, lanes=LANES, want=want_b)

    print(f"phase (c): {' '.join(DS_SEARCH)} against the serial oracle",
          flush=True)
    want_c = clock.run("c oracle", lambda: oracle_optimum(env, *DS_SEARCH))
    solve_phase(env, clock, "c", *DS_SEARCH, lanes=LANES, want=want_c)

    print(f"phase (d): service, {len(SERVICE_MIX)} requests over {SLOTS} "
          f"slots", flush=True)
    want_d = clock.run("d oracle", lambda: {
        i: oracle_optimum(env, fam, spec)
        for i, (fam, spec) in enumerate(SERVICE_MIX)})
    service_phase(env, clock, "d", SERVICE_MIX, lanes=LANES, want=want_d)


def four_chips(env, clock, devices) -> None:
    mesh = env.jax.make_mesh((4,), ("workers",), devices=devices[:4])
    per_dev = LANES // 4

    print(f"phase (4b): {' '.join(VC_SEARCH)} on a 4-chip mesh vs one chip",
          flush=True)
    want_b = clock.run("4b oracle", lambda: oracle_optimum(env, *VC_SEARCH))
    one = solve_phase(env, clock, "4b one-chip", *VC_SEARCH, lanes=LANES,
                      backends=("pallas",), want=want_b)
    four = solve_phase(env, clock, "4b mesh", *VC_SEARCH, lanes=per_dev,
                       mesh=mesh, backends=("pallas",), want=want_b)
    mesh_lanes = four["pallas"][1].lanes
    rows = lane_rows(mesh_lanes.idx)
    print(f"  4b mesh lane rows per device: {rows}", flush=True)
    check(len(rows) == 4 and all(r == per_dev for _, r in rows),
          f"4b: lanes not spread over 4 devices: {rows}")
    check(one["pallas"][0]["optimum"] == four["pallas"][0]["optimum"],
          "4b: mesh optimum differs from the one-chip optimum")

    print("phase (4d): sharded service vs one-chip service", flush=True)
    want_d = clock.run("4d oracle", lambda: {
        i: oracle_optimum(env, fam, spec)
        for i, (fam, spec) in enumerate(SERVICE_MIX)})
    one_svc = service_phase(env, clock, "4d one-chip", SERVICE_MIX,
                            lanes=LANES, backends=("pallas",), want=want_d)
    four_svc = service_phase(env, clock, "4d mesh", SERVICE_MIX,
                             lanes=per_dev, mesh=mesh, backends=("pallas",),
                             want=want_d)
    rows = lane_rows(four_svc["pallas"][1].lanes.idx)
    print(f"  4d mesh lane rows per device: {rows}", flush=True)
    check(len(rows) == 4 and all(r == per_dev for _, r in rows),
          f"4d: lanes not spread over 4 devices: {rows}")
    check(one_svc["pallas"][0]["results"] == four_svc["pallas"][0]["results"],
          "4d: sharded service results differ from the one-chip service")


class Env:
    """The repository's entry points, imported once ``src/`` is on the
    path (the script itself imports nothing of the repo at load time)."""

    def __init__(self):
        import jax

        from repro import registry
        from repro.core.distributed import make_round
        from repro.core.engine import init_lanes
        from repro.service import SolveRequest
        from repro.service.batch_problem import StackedSpec, StackedTables
        from repro.solver import Solver, SolverConfig

        self.jax, self.registry = jax, registry
        self.make_round, self.init_lanes = make_round, init_lanes
        self.SolveRequest, self.StackedSpec = SolveRequest, StackedSpec
        self.StackedTables = StackedTables
        self.Solver, self.SolverConfig = Solver, SolverConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(d) on one chip; 4: only the "
                         "multi-chip phase and what it is compared with")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"FAIL: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"FAIL: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"FAIL: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 2

    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    env = Env()
    clock = Clock(jax, devices[:args.chips])
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(env, clock)
        else:
            four_chips(env, clock, devices)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(f"all phases passed: compile_s={clock.compile_s:.2f} "
          f"wall_s={time.perf_counter() - t0:.2f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
