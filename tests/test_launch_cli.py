"""Launcher capability checks (ISSUE 3 satellite, registry-driven since
ISSUE 4).

``launch/solve.py`` contains zero per-problem knowledge: ``--problem``
choices, instance parsing and ``--backend`` validation all come from the
``repro.registry`` ProblemSpec table.  A family gains a CLI the moment it
registers (demonstrated end-to-end by subset sum, which had no CLI before
the registry existed), and a jnp-only family still fails fast with the
capability list in the error.
"""

import dataclasses
import sys

import pytest

from repro import registry
from repro.launch import solve
from repro.problems import (PROBLEM_FACTORIES, make_subset_sum,
                            problem_backends)
from repro.solver import Solver


def test_factories_advertise_backends():
    assert problem_backends("vc") == ("jnp", "pallas")
    assert problem_backends("ds") == ("jnp", "pallas")
    assert make_subset_sum.backends == ("jnp",)     # no bitset table
    # The deprecated factory table is a registry view, never a fork.
    assert set(PROBLEM_FACTORIES) == set(registry.names())


def run_main(argv, monkeypatch):
    monkeypatch.setattr(sys, "argv", ["solve"] + argv)
    solve.main()


def optimum_of(out: str) -> str:
    line = [l for l in out.splitlines() if "optimum=" in l][0]
    return line.split("optimum=")[1].split()[0]


def test_solve_cli_accepts_ds_pallas(monkeypatch, capsys):
    """The stale fail-fast is gone: a ds Pallas solve runs end-to-end and
    prints the same optimum as the jnp backend."""
    args = ["--problem", "ds", "--instance", "gnp:10:30:4", "--lanes", "4",
            "--steps-per-round", "16"]
    run_main(args + ["--backend", "pallas"], monkeypatch)
    out_pallas = capsys.readouterr().out
    run_main(args + ["--backend", "jnp"], monkeypatch)
    out_jnp = capsys.readouterr().out
    assert optimum_of(out_pallas) == optimum_of(out_jnp)


def test_solve_cli_subset_sum_end_to_end(monkeypatch, capsys):
    """ISSUE 4 satellite: subset sum is a registration, not a plumbing
    project — ``--problem ss`` works end-to-end with no launcher edits and
    its optimum matches the registered serial oracle."""
    run_main(["--problem", "ss", "--instance", "ss:12:3", "--lanes", "4",
              "--steps-per-round", "16"], monkeypatch)
    out = capsys.readouterr().out
    handle = registry.problem("ss", "ss:12:3")
    assert int(optimum_of(out)) == Solver().oracle(handle).best


def test_solve_cli_rejects_unsupported_backend(monkeypatch):
    """A family that does not register pallas still fails fast, with the
    registered capability list in the error message."""
    spec = registry.get("ds")
    jnp_only = dataclasses.replace(
        spec, backends=("jnp",),
        builder=lambda *a, **k: pytest.fail(
            "factory must not be called on a rejected run"))
    monkeypatch.setitem(registry._REGISTRY, "ds", jnp_only)
    with pytest.raises(SystemExit):
        run_main(["--problem", "ds", "--instance", "gnp:10:30:4",
                  "--backend", "pallas"], monkeypatch)


def test_solve_cli_rejects_bad_instance_spec(monkeypatch):
    """Instance-spec errors surface as argparse errors, not tracebacks."""
    with pytest.raises(SystemExit):
        run_main(["--problem", "vc", "--instance", "bogus:1:2"],
                 monkeypatch)
    with pytest.raises(SystemExit):
        run_main(["--problem", "ss", "--instance", "reg:10:4:1"],
                 monkeypatch)


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    """The persistent compile cache is placed from outside: the env var
    wins, otherwise the fixed ``.jax_cache/`` at the checkout root."""
    import pathlib

    from repro import compile_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV_VAR)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.cache_dir() == str(root / ".jax_cache")
