"""The device round loop under ``Solver.solve``
(``distributed.jit_round_loop``): one dispatch runs as many rounds as the
host lets it, and a solve does the same work, round for round, as a solve
that takes every round back to the host.

A listener (``on_event``) makes the host take each round back, so each
case is solved with and without one and the two results must agree in
every count and in the payload.  Dispatches are counted by their
``repro.solve.dispatch`` spans.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import obs, registry
from repro.core import checkpoint as ckpt
from repro.solver import Solver, SolverConfig

PROBLEMS = {"vc": registry.problem("vc", "gnp:30:20:3"),
            "ds": registry.problem("ds", "gnp:22:20:4")}


def solve(monkeypatch, config, handle, listener=None):
    """``Solver(config).solve(handle)`` and the dispatches it made."""
    names = []
    real = obs.span

    def span(name):
        names.append(name)
        return real(name)

    with monkeypatch.context() as m:
        m.setattr(obs, "span", span)
        res = Solver(config, on_event=listener).solve(handle)
    return res, names.count("repro.solve.dispatch")


def assert_same_work(a, b):
    assert a.stats == b.stats
    np.testing.assert_array_equal(np.asarray(a.payload),
                                  np.asarray(b.payload))


@pytest.mark.parametrize("family", sorted(PROBLEMS))
@pytest.mark.parametrize("bootstrap_rounds", [0, 2])
def test_one_dispatch_does_the_work_of_one_a_round(monkeypatch, family,
                                                   bootstrap_rounds):
    handle = PROBLEMS[family]
    config = SolverConfig(lanes=8, steps_per_round=16,
                          bootstrap_rounds=bootstrap_rounds)
    plain, plain_dispatches = solve(monkeypatch, config, handle)
    events = []
    each, each_dispatches = solve(monkeypatch, config, handle, events.append)

    assert_same_work(plain, each)
    rounds = plain.stats.rounds
    assert rounds > bootstrap_rounds + 1
    assert plain.stats.best == Solver().oracle(handle).best
    assert plain_dispatches == 1 + (bootstrap_rounds > 0)
    assert each_dispatches == rounds
    assert [e.round for e in events if e.kind == "round"] == list(
        range(bootstrap_rounds + 1, rounds + 1))


def test_a_round_budget_stops_the_loop_with_work_open(monkeypatch):
    config = SolverConfig(lanes=8, steps_per_round=16, max_rounds=3)
    res, dispatches = solve(monkeypatch, config, PROBLEMS["vc"])
    assert res.stats.rounds == 3
    assert int(np.asarray(res.lanes.active).sum()) > 0
    assert dispatches == 1


def test_checkpoints_land_on_the_same_rounds_with_the_same_state(
        monkeypatch, tmp_path):
    path = str(tmp_path / "run.ckpt")
    config = SolverConfig(lanes=8, steps_per_round=16, checkpoint_every=2,
                          checkpoint_path=path)
    real = ckpt.save

    def saves(listener=None):
        written = []

        def save(p, lanes, extra=None):
            real(p, lanes, extra)
            with np.load(p) as z:
                written.append({k: z[k] for k in z.files})

        with monkeypatch.context() as m:
            m.setattr(ckpt, "save", save)
            res, dispatches = solve(monkeypatch, config, PROBLEMS["vc"],
                                    listener)
        return res, dispatches, written

    plain, plain_dispatches, plain_written = saves()
    events = []
    each, _, each_written = saves(events.append)

    assert_same_work(plain, each)
    rounds = plain.stats.rounds
    assert [e.round for e in events if e.kind == "checkpoint"] == list(
        range(2, rounds + 1, 2))
    assert len(plain_written) == len(each_written) == rounds // 2 > 1
    for a, b in zip(plain_written, each_written):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert plain_dispatches == -(-rounds // 2)


def test_a_resume_pool_larger_than_the_lanes_is_fed_a_round_at_a_time(
        monkeypatch, tmp_path):
    handle = registry.problem("vc", "gnp:40:20:3")
    path = str(tmp_path / "wide.ckpt")
    Solver(SolverConfig(lanes=32, steps_per_round=16, max_rounds=3,
                        bootstrap_rounds=1, bootstrap_steps=4,
                        checkpoint_every=3, checkpoint_path=path)).solve(
                            handle)
    _, pool = ckpt.restore(path, handle.build(), 6)
    assert pool, "the checkpoint must hold more tasks than 6 lanes"

    config = SolverConfig(lanes=6, steps_per_round=16, resume_from=path)
    plain, plain_dispatches = solve(monkeypatch, config, handle)
    each, each_dispatches = solve(monkeypatch, config, handle, lambda e: 0)

    assert_same_work(plain, each)
    assert plain.stats.best == Solver().oracle(handle).best
    assert each_dispatches == each.stats.rounds
    assert 1 < plain_dispatches < each_dispatches


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import numpy as np
from repro import obs, registry
from repro.solver import Solver, SolverConfig

assert len(jax.devices()) == 8, jax.devices()
mesh = jax.make_mesh((2, 4), ("data", "model"))
real, names = obs.span, []
obs.span = lambda name: names.append(name) or real(name)
out = {}
for family, spec, lanes in (("vc", "gnp:16:35:5", 4),
                            ("ds", "gnp:12:30:9", 2)):
    handle = registry.problem(family, spec)
    config = SolverConfig(lanes=lanes, steps_per_round=8, mesh=mesh,
                          bootstrap_rounds=3, bootstrap_steps=4)
    for listen in (False, True):
        del names[:]
        res = Solver(config, on_event=(lambda e: 0) if listen else None
                     ).solve(handle)
        out[f"{family}_{listen}"] = dict(
            stats=list(res.stats),
            payload=np.asarray(res.payload).tolist(),
            dispatches=names.count("repro.solve.dispatch"),
            oracle=Solver().oracle(handle).best)
print("RESULT " + json.dumps(out))
"""


def test_the_mesh_loop_does_the_work_of_one_dispatch_a_round():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    out = json.loads(line[-1][len("RESULT "):])
    for family in ("vc", "ds"):
        plain, each = out[f"{family}_False"], out[f"{family}_True"]
        assert plain["stats"] == each["stats"], family
        assert plain["payload"] == each["payload"], family
        best, rounds = plain["stats"][0], plain["stats"][1]
        assert best == plain["oracle"], family
        assert rounds > 4, family
        assert plain["dispatches"] == 2, family
        assert each["dispatches"] == rounds, family


def test_every_budget_is_the_same_program(monkeypatch):
    """The warm-up's one-round solve and a whole solve lower to one
    program, so the compile cache that the first fills serves the second;
    the program keeps the round's name."""
    import repro.solver
    texts = []
    real = repro.solver.jit_round_loop

    def recording(body, problem, mesh=None):
        fn = real(body, problem, mesh)

        def call(lanes, budget):
            texts.append((int(budget), fn.lower(lanes, budget).as_text()))
            return fn(lanes, budget)
        return call

    monkeypatch.setattr(repro.solver, "jit_round_loop", recording)
    for max_rounds in (1, 100000):
        Solver(SolverConfig(lanes=8, steps_per_round=16,
                            max_rounds=max_rounds)).solve(PROBLEMS["vc"])
    (one, first), (whole, second) = texts
    assert (one, whole) == (1, 100000)
    assert first == second
    assert "round_fn" in first.splitlines()[0]
