"""The control of the comparison: a cell run with the program's anytime
path switched on, which breaks the guarantee the configurations state
(every answer a proven optimum).  ``correct`` has to come out false.

  python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

Solve cells cap every solve at ``--rounds`` rounds (``SolverConfig
.max_rounds``; the panel's instances need far more), so a solve returns
its incumbent with work still open.  Service cells give every request a
node budget of ``--node-budget`` (``SolveRequest.node_budget``), so larger
requests are evicted with their incumbent.  One process runs every seed;
each prints its checks and its result line.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import sys
import time

import harness


def control_config(config: dict, rounds: int, node_budget: int) -> dict:
    if config["entry"] == "solve":
        return dict(config, max_rounds=rounds)
    return dict(config, node_budget=node_budget)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--node-budget", type=int, default=2000)
    args = ap.parse_args(argv)
    harness.add_paths()
    try:
        cell = harness.Cell(harness.load_json(harness.ROOT / "BENCHMARK.json"),
                            args.workload)
        devices = harness.start_program(cell.chips)
    except harness.BenchError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 3
    cell.config = control_config(cell.config, args.rounds, args.node_budget)
    verdicts = []
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(cell, seed, args.seconds, False, devices,
                                  t_start)
        print(f"control seed {seed}: correct={result['correct']} checks="
              f"{ {k: c['value'] for k, c in result['checks'].items()} }",
              flush=True)
        verdicts.append(result["correct"])
        t_start = time.perf_counter()
    print(f"control: {verdicts.count(False)} of {len(verdicts)} runs not "
          f"correct", flush=True)
    return 0 if not any(verdicts) else 1


if __name__ == "__main__":
    sys.exit(main())
