"""Dry-run the PAPER'S OWN technique on the production meshes.

Lowers one distributed steal round (expand R nodes -> intra-device steal
-> cross-device steal -> incumbent pmin -> termination psum) for a
512-vertex Vertex Cover instance over the 16x16 (256-chip) and 2x16x16
(512-chip) meshes, and runs the roofline analysis of ``repro.roofline``
on its compiled HLO.

This quantifies the paper's central claim at pod scale: tasks are O(d)
int8 index vectors, so the steal phase's collective payload is tiny
relative to the compute phase — the table shows collective bytes per
round of a few MB against hundreds of ms of node-expansion compute.

  PYTHONPATH=src python -m repro.launch.solver_dryrun [--multi-pod]
"""

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

# ruff: noqa: E402
import argparse
import json

import jax
import numpy as np

from repro import registry
from repro.core.distributed import make_distributed_round
from repro.core.engine import init_lanes
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.roofline import analyze_hlo

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "benchmarks", "artifacts",
                            "dryrun")


def run(multi_pod: bool, lanes_per_device: int = 8,
        steps_per_round: int = 256, problem: str = "vc",
        instance: str = "reg:512:4:1"):
    """Lower one distributed round of any registered problem family over
    the production mesh (registry-driven — no per-problem code here)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(mesh.devices.shape))
    spec = registry.get(problem)
    g = spec.parse(instance)
    prob = spec.build(g)

    fn = make_distributed_round(prob, mesh, steps_per_round, max_ship=16)
    lanes = init_lanes(prob, lanes_per_device * n_dev, seed_root=False)
    ab = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), lanes)
    with mesh:
        lowered = fn.lower(ab)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    counts = analyze_hlo(compiled.as_text())
    terms = counts.terms(PEAK_FLOPS_BF16, HBM_BW, ICI_BW)
    out = {
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "lanes_total": lanes_per_device * n_dev,
        "steps_per_round": steps_per_round,
        "problem": problem,
        "instance": spec.label(g),
        "peak_bytes": int(mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes
                          + mem.output_size_in_bytes
                          - mem.alias_size_in_bytes),
        "collective_bytes_per_round_per_dev": counts.collective_bytes,
        "per_collective": counts.per_collective,
        "hbm_bytes_per_dev": counts.hbm_bytes,
        "compute_s": terms["compute_s"],
        "memory_s": terms["memory_s"],
        "collective_s": terms["collective_s"],
    }
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    path = os.path.join(
        ARTIFACT_DIR, f"solver__round__{'mp' if multi_pod else 'sp'}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--problem", default="vc",
                    help="registered problem family (repro.registry)")
    ap.add_argument("--instance", default="reg:512:4:1")
    args = ap.parse_args()
    if args.both:
        run(False, problem=args.problem, instance=args.instance)
        run(True, problem=args.problem, instance=args.instance)
    else:
        run(args.multi_pod, problem=args.problem, instance=args.instance)


if __name__ == "__main__":
    main()
