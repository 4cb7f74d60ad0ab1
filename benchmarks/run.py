"""Benchmark harness entry point: one module per paper table/figure.

  table1  — PARALLEL-VERTEX-COVER scaling (paper Table I)
  table2  — PARALLEL-DOMINATING-SET scaling (paper Table II)
  fig10   — T_S/T_R steal-traffic gap growth (paper Fig. 10)
  kernels — Pallas kernel micro (shapes, ref timings, interpret deltas)
  service — continuous-batching throughput vs sequential solves
  latency — scheduling policies on a Poisson trace (p50/p95, deadlines)

``python -m benchmarks.run [--quick] [--only NAME]``
CSV artifacts land in benchmarks/artifacts/.
"""

from __future__ import annotations

import argparse
import glob
import os
import subprocess
import sys
import time

from benchmarks import (fig10_steal_traffic, kernel_micro,
                        service_latency, service_throughput,
                        table1_vertex_cover, table2_dominating_set)
from benchmarks.common import ART_DIR
from repro import compile_cache

SUITES = [
    ("table1", table1_vertex_cover.main),
    ("table2", table2_dominating_set.main),
    ("fig10", fig10_steal_traffic.main),
    ("kernels", kernel_micro.main),
    ("service", service_throughput.main),
    ("latency", service_latency.main),
]


def trace_reports() -> None:
    """Summarize every trace a suite left behind (DESIGN.md §8).

    Suites that run with telemetry write JSONL traces under
    ``artifacts/traces/``; each one gets a sibling ``.report.txt`` from
    ``tools/trace_report.py`` — the standard load-balance artifact.  A
    schema violation (exit 2) fails the whole harness run.
    """
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_report.py")
    for trace in sorted(glob.glob(os.path.join(ART_DIR, "traces",
                                               "*.jsonl"))):
        proc = subprocess.run([sys.executable, tool, trace],
                              capture_output=True, text=True)
        if proc.returncode != 0:
            raise SystemExit(
                f"trace_report failed on {trace}:\n{proc.stderr}")
        report = trace[:-len(".jsonl")] + ".report.txt"
        with open(report, "w") as f:
            f.write(proc.stdout)
        print(f"trace report -> {report}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced worker counts / shapes")
    ap.add_argument("--only", default=None)
    # Anything after `--` is forwarded to the selected suite's own CLI,
    # e.g. `python -m benchmarks.run --only service -- --devices 1,2,4`.
    args, extra = ap.parse_known_args()
    compile_cache.enable()
    if extra and extra[0] == "--":
        extra = extra[1:]
    if extra and not args.only:
        raise SystemExit("suite args (`-- ...`) require --only NAME")
    for name, fn in SUITES:
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"== {name} ==", flush=True)
        if extra:
            mod = sys.modules[fn.__module__]
            if not hasattr(mod, "cli"):
                raise SystemExit(f"suite {name} takes no extra args")
            mod.cli(extra + (["--quick"] if args.quick else []))
        else:
            fn(quick=args.quick)
        print(f"== {name} done in {time.time()-t0:.1f}s ==", flush=True)
    trace_reports()


if __name__ == "__main__":
    main()
