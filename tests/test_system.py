"""End-to-end system tests: the examples and the solver launchers run."""

import os
import subprocess
import sys

REPO = os.path.join(os.path.dirname(__file__), "..")


def run_script(args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable] + args, env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    return proc.stdout


def test_quickstart_example():
    out = run_script(["examples/quickstart.py"])
    assert "optimum matches the serial oracle" in out


def test_solver_cli_with_checkpoint(tmp_path):
    ck = str(tmp_path / "s.ckpt")
    out = run_script(["-m", "repro.launch.solve", "--problem", "vc",
                      "--instance", "gnp:20:30:5", "--lanes", "8",
                      "--ckpt", ck])
    assert "optimum=" in out


def test_solver_cli_ds_pallas_solves():
    """--backend pallas with --problem ds used to fail fast (ds had no
    kernel path); since the bitset_ops layer (DESIGN.md §5) it must solve —
    the capability check is factory-driven (tests/test_launch_cli.py covers
    the rejection path for jnp-only factories)."""
    out = run_script(["-m", "repro.launch.solve", "--problem", "ds",
                      "--backend", "pallas", "--instance", "gnp:10:30:1",
                      "--lanes", "4", "--steps-per-round", "16"])
    assert "optimum=" in out


def test_serve_solver_cli_smoke():
    out = run_script(["-m", "repro.launch.serve_solver",
                      "--instances", "vc:gnp:12:30:5,ds:gnp:10:30:7",
                      "--lanes", "8", "--slots", "2",
                      "--steps-per-round", "16"])
    assert "drained 2 requests" in out
