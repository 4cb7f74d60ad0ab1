"""The benchmark harness: one cell, one run, one result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

  bench/configs/<config>.json    the deployment as it is run
  bench/traffic/<mix>.json       a mix's parameters; its ``kind`` names the
                                 generator ``bench/traffic/<kind>.py``
  bench/entries/<entry>.py       the program's entry point a configuration
                                 drives (``entry`` in its file)
  bench/metrics/<metric>.py      a per-layer metric's reader

A run: find the chips (a TPU, as many as the cell asks; never the CPU),
turn on the persistent compile cache, set up and warm up (``setup_s``),
measure for ``--seconds`` with the profiler off (``--trace 0``) or
recording the window's start (``--trace 1``), read the peak memory, free
the program's state, compare every answer with the plain reference, and
print the result as the last line of standard output.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, bad
    benchmark files)."""


# -- finding things by name ------------------------------------------------

def load_json(path: pathlib.Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    if not path.is_file():
        raise BenchError(f"missing benchmark module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, bench: pathlib.Path = BENCH) -> dict:
    return load_json(bench / "configs" / f"{name}.json")


def load_mix(name: str, bench: pathlib.Path = BENCH) -> dict:
    return load_json(bench / "traffic" / f"{name}.json")


def generator(kind: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "traffic" / f"{kind}.py")


def entry(name: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "entries" / f"{name}.py")


def reader(metric: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "metrics" / f"{metric}.py")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, benchmark: dict, name: str,
                 bench: pathlib.Path = BENCH):
        cells = {w["name"]: w for w in benchmark["workloads"]}
        if name not in cells:
            raise BenchError(f"unknown workload {name!r} (known: "
                             f"{', '.join(cells)})")
        w = cells[name]
        self.name, self.chips = name, int(w["chips"])
        self.config = load_config(w["config"], bench)
        self.mix = load_mix(w["traffic"], bench)
        self.end_to_end = [m for m in benchmark["end_to_end"]
                           if applies(m, name)]
        self.per_layer = [m for m in benchmark["per_layer"]
                          if applies(m, name)]
        self.bench = bench


# -- the run ---------------------------------------------------------------

class Context:
    """What an entry gets: the cell, the run's arguments, the devices, and
    the set-up clock."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices: list, t_start: float):
        self.cell, self.config, self.mix = cell, cell.config, cell.mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices[:cell.chips]
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.traffic = generator(cell.mix["kind"], cell.bench).make(
            cell.config, cell.mix, seed)

    def mesh(self):
        """None on one chip, else a 1-D ``workers`` mesh over the cell's
        chips."""
        if self.cell.chips == 1:
            return None
        import jax
        return jax.make_mesh((self.cell.chips,), ("workers",),
                             devices=self.devices)

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self.compiles_at_setup = (CompileCount.hits, CompileCount.misses)

    def tracer(self) -> "Tracer":
        return Tracer(self.trace)


class Tracer:
    """The profiler around the start of the window (``--trace 1`` only);
    the ``harness.window`` annotation marks what it recorded."""

    def __init__(self, on: bool):
        self.on, self.active, self.path = on, False, None
        self._window = None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        self._window = jax.profiler.TraceAnnotation("harness.window")
        self._window.__enter__()
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.path = str(TRACE_DIR)


class CompileCount:
    """Programs the persistent cache served and programs compiled anew,
    from JAX's own monitoring events (one listener per process)."""

    hits = misses = 0
    _listening = False

    @classmethod
    def listen(cls) -> None:
        if cls._listening:
            return
        import jax

        def on(event: str, **_) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                cls.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                cls.misses += 1

        jax.monitoring.register_event_listener(on)
        cls._listening = True


def span(name: str):
    """A host annotation the trace reduction reads (cheap when off)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Record:
    """What an entry hands back: its end-to-end numbers, its answers, and
    the host-side readings per-layer readers use."""

    def __init__(self, e2e: Dict[str, float], answers: list,
                 host: Dict[str, Any], trace_path: Optional[str] = None,
                 lines: Optional[List[str]] = None):
        self.e2e, self.answers, self.host = e2e, answers, host
        self.trace_path, self.lines = trace_path, lines or []


class View:
    """What a per-layer reader gets: the entry's host readings and the
    reduced trace (None without one)."""

    def __init__(self, host: Dict[str, Any], summary):
        self.host, self.trace = host, summary


def find_devices(chips: int):
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise BenchError(f"no TPU found (JAX platform {dev.platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    peaks = load_json(BENCH / "peaks.json")
    if dev.device_kind not in peaks["devices"]:
        raise BenchError(f"device kind {dev.device_kind!r} is not in "
                         f"bench/peaks.json")
    return devices


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    all values at or below it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, -(-len(vals) * q // 100))
    return float(vals[int(rank) - 1])


def per_layer(cell: Cell, view: View) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        value = reader(m["name"], cell.bench).read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(cell: Cell, ctx: Context, record: Record,
               correct: bool) -> Dict[str, dict]:
    """The cell's end-to-end metrics; a sound run must give each one (a
    run that is not correct may lack one, as when nothing was answered)."""
    values = dict(record.e2e, setup_s=ctx.setup_s)
    out = {}
    for m in cell.end_to_end:
        if values.get(m["name"]) is None:
            if correct:
                raise BenchError(f"entry gave no {m['name']!r}")
            continue
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list, t_start: float) -> dict:
    """Run ``cell`` once on ``devices``; return the result object."""
    import judge
    import trace_reduce

    CompileCount.listen()
    ctx = Context(cell, seed, seconds, trace, devices, t_start)
    record = entry(cell.config["entry"], cell.bench).run(ctx)
    hits0, misses0 = ctx.compiles_at_setup
    record.lines.append(
        f"after set-up: {CompileCount.misses - misses0} programs compiled, "
        f"{CompileCount.hits - hits0} served by the persistent cache")
    memory = peak_bytes(ctx.devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    summary = None
    if trace:
        path = trace_reduce.find_xplane(record.trace_path)
        ids = [d.id for d in ctx.devices]
        summary = trace_reduce.Summary(trace_reduce.load(path, ids))
        device["busy_s"] = summary.busy_ns() / 1e9
        device["window_s"] = summary.window_ns / 1e9
    gc.collect()
    t_judge = time.perf_counter()
    verdict = judge.compare(record.answers)
    record.lines.append(f"comparison with the reference: "
                        f"{time.perf_counter() - t_judge:.3f} s")
    if trace:
        metrics = per_layer(cell, View(record.host, summary))
    else:
        metrics = end_to_end(cell, ctx, record, verdict.correct)
    result = {"correct": verdict.correct, "attempted": verdict.attempted,
              "failed": verdict.failed, "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    for line in record.lines:
        print(line, flush=True)
    result["checks"] = {k: {"value": v, "limit": judge.LIMITS[k]}
                        for k, v in verdict.numbers.items()}
    return result


def report(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def start_program(chips: int) -> list:
    """Put the program on the path, find the chips and turn on the
    persistent compile cache; return the devices."""
    if not (SRC / "repro").is_dir():
        raise BenchError(f"no program (src/repro) next to {BENCH}")
    sys.path.insert(0, str(SRC))
    devices = find_devices(chips)
    import jax
    from repro import compile_cache
    # The cache is the checkout's own, whatever the environment names:
    # runs of two checkouts never share compiled programs.
    os.environ[compile_cache.ENV_VAR] = str(CACHE_DIR)
    path = compile_cache.enable()
    # Every program, however quick to compile, goes to the cache and stays
    # there: only the first run of a cell in a checkout compiles.  This is
    # part of the deployment each configuration states
    # (``assumed.compile_cache``).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    print(f"compile cache: {path}", flush=True)
    from repro.solver import SolverConfig
    print(f"backend: {SolverConfig().backend} (the program's default)",
          flush=True)
    return devices


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(load_json(ROOT / "BENCHMARK.json"), args.workload)
        devices = start_program(cell.chips)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices, t_start)
    except BenchError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 3
    report(result)
    return 0


def add_paths() -> None:
    """Make the harness's own modules importable by name."""
    for path in (str(BENCH / "traffic"), str(BENCH)):
        if path not in sys.path:
            sys.path.insert(0, path)
