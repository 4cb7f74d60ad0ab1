"""Run one cell at a list of settings, in one process, one run each.

  python bench/sweep.py --workload vc_c125.solve \
      --set lanes=512,1024,4096,16384 --seconds 1 --trace 0,1
  python bench/sweep.py --workload <open-loop service cell> \
      --set arrivals.rate=10,20,30 --seconds 15

``--set KEY=V1,V2,...`` changes one setting of the cell's configuration, or
of its traffic mix where the configuration has no such key; a dotted KEY
names a setting inside a group.  Every run prints the cell's own lines and
its metrics (end to end with ``--trace 0``, per layer with ``--trace 1``).
The lane sweep finds the lane count a deployment picks, the one with the
shortest time to optimum; the rate sweep finds a service's knee, the
highest rate at which the queue does not grow over the window.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import harness


def with_setting(cell: harness.Cell, key: str, value) -> None:
    path = key.split(".")
    attr = "config" if path[0] in cell.config else "mix"
    group = copy.deepcopy(getattr(cell, attr))
    node = group
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value
    setattr(cell, attr, group)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", required=True, metavar="KEY=V1,V2,...")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    key, values = args.set.split("=", 1)
    harness.add_paths()
    cell = harness.Cell(harness.load_json(harness.ROOT / "BENCHMARK.json"),
                        args.workload)
    devices = harness.start_program(cell.chips)
    for value in (json.loads(v) for v in values.split(",")):
        with_setting(cell, key, value)
        for trace in (bool(int(t)) for t in args.trace.split(",")):
            result = harness.run_cell(cell, args.seed, args.seconds, trace,
                                      devices, time.perf_counter())
            metrics = {k: v["value"] for k, v in result["metrics"].items()}
            print(f"sweep {key}={value} trace={int(trace)} correct="
                  f"{result['correct']} {metrics} "
                  f"memory_peak_bytes={result['device']['memory_peak_bytes']}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
