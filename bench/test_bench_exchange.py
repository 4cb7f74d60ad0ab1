"""A four-chip solve's answers pass through the cross-chip exchange: with
the tasks it ships lost, ``correct`` comes out false.  Runs the solve cell
on a 4-chip mesh at a small size on four virtual CPU devices, in a child
process (the device count is fixed before JAX starts)."""

import json
import os
import subprocess
import sys

import harness

CHILD = r"""
import json, sys, time
sys.path[:0] = [{bench!r}, {traffic!r}, {src!r}]
import jax, jax.numpy as jnp
import harness
from repro.core import steal

cell = harness.Cell(harness.load_json(harness.ROOT / "BENCHMARK.json"),
                    "vc_c125.solve")
cell.chips = 4
cell.config.update(graph={{"model": "gnp", "n": 40, "p": 0.15}}, lanes=8)
cell.mix.update(instance_seeds=[4])
if {lose!r}:
    install = steal.install_tasks

    def lost(problem, lanes, bits, depth, inst, claim, cross=False):
        if cross:                       # shipped, never installed
            claim = jnp.zeros_like(claim)
        return install(problem, lanes, bits, depth, inst, claim, cross=cross)

    steal.install_tasks = lost
result = harness.run_cell(cell, 2**31 + 9, 0.5, False, jax.devices(),
                          time.perf_counter())
print(json.dumps(result))
"""


def run_child(lose: bool) -> dict:
    code = CHILD.format(bench=str(harness.BENCH),
                        traffic=str(harness.BENCH / "traffic"),
                        src=str(harness.SRC), lose=lose)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_four_chip_solve_is_correct_and_fails_without_the_exchange():
    sound = run_child(lose=False)
    assert sound["correct"], sound["checks"]
    broken = run_child(lose=True)
    assert not broken["correct"]
    assert broken["checks"]["wrong_optimum"]["value"] == broken["attempted"]
