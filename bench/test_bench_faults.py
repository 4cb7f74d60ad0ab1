"""``correct`` comes out false when the timed path is broken underneath, and
for the control (the program's anytime path, which breaks the guarantee of
a proven optimum).  Each test drives a whole run of a cell at a small size
on the CPU, past the harness's look for a chip."""

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import control
import harness

BENCHMARK = harness.load_json(harness.ROOT / "BENCHMARK.json")
ARRIVALS = {"open": {"process": "poisson", "rate": 4.0},
            "backlog": {"process": "backlog", "depth": 32}}


def small(name):
    """A cell at a small size: the solve cell of ``BENCHMARK.json``, or a
    service cell (``open`` / ``backlog`` arrivals) for the ``serve``
    entry, which no cell of ``BENCHMARK.json`` runs yet."""
    if name in ARRIVALS:
        return SimpleNamespace(
            name=f"service.{name}", chips=1, bench=harness.BENCH,
            config={"entry": "serve", "lanes": 32, "steps_per_round": 64,
                    "slots": 4, "max_n": 30, "requests": [
                        {"family": "vc", "p": 0.15, "n": [20, 30],
                         "share": 0.5},
                        {"family": "ds", "p": 0.15, "n": [15, 25],
                         "share": 0.5}]},
            mix={"kind": "service", "pool_seed": 1, "pool_size": 8,
                 "arrivals": ARRIVALS[name], "drain_s": 1.0},
            end_to_end=[{"name": "setup_s", "unit": "s"}], per_layer=[])
    cell = harness.Cell(BENCHMARK, name)
    cell.config.update(graph={"model": "gnp", "n": 36, "p": 0.15},
                       lanes=32, max_rounds=6)
    cell.mix.update(instance_seeds=[1, 2])
    cell.chips = 1
    return cell


def run(cell, seconds=1.0):
    return harness.run_cell(cell, 2**31 + 5, seconds, False,
                            jax.devices()[:1], time.perf_counter())


def still_round(*args, **kwargs):
    """A round that returns its state unchanged, with work still open."""
    def round_fn(lanes, *rest):
        return lanes, jnp.ones(lanes.best.shape, jnp.int32)
    return round_fn


@pytest.mark.parametrize("name", ["vc_c125.solve", "open"])
def test_a_sound_run_is_correct(name):
    result = run(small(name))
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_solve_state_unchanged(monkeypatch):
    import repro.solver
    monkeypatch.setattr(repro.solver, "make_round", still_round)
    result = run(small("vc_c125.solve"))
    assert not result["correct"]
    assert result["checks"]["unfinished"]["value"] == result["attempted"]


def test_solve_answer_altered(monkeypatch):
    from repro.solver import Solver
    solve = Solver.solve

    def altered(self, problem):
        res = solve(self, problem)
        return res._replace(stats=res.stats._replace(best=res.stats.best + 1))

    monkeypatch.setattr(Solver, "solve", altered)
    result = run(small("vc_c125.solve"))
    assert not result["correct"]
    assert result["checks"]["wrong_optimum"]["value"] == result["attempted"]


def test_service_state_unchanged(monkeypatch):
    from repro.service.driver import SolverService
    step_round = SolverService.step_round
    setup_done = harness.Context.setup_done

    def stalled(self):
        self._round = still_round()
        return step_round(self)

    def break_after_setup(ctx):
        setup_done(ctx)          # set-up drained its requests soundly
        monkeypatch.setattr(SolverService, "step_round", stalled)

    monkeypatch.setattr(harness.Context, "setup_done", break_after_setup)
    result = run(small("open"))
    assert not result["correct"]
    assert result["checks"]["missing"]["value"] == result["attempted"]


def test_service_answer_altered(monkeypatch):
    import repro.service.driver as driver
    original = driver.RequestResult

    def altered(**kw):
        return original(**dict(kw, optimum=kw["optimum"] + 1))

    monkeypatch.setattr(driver, "RequestResult", altered)
    result = run(small("open"))
    assert not result["correct"]
    assert result["checks"]["wrong_optimum"]["value"] == result["attempted"]


@pytest.mark.parametrize("name", ["open", "backlog"])
def test_service_half_the_batch_left_out(monkeypatch, name):
    from repro.service.driver import SolverService
    submit = SolverService.submit

    def half(self, request):
        if request.rid % 2 == 0 or request.rid >= 1 << 30:
            return submit(self, request)
        return None

    monkeypatch.setattr(SolverService, "submit", half)
    result = run(small(name))
    assert not result["correct"]
    assert result["checks"]["missing"]["value"] == result["attempted"] // 2


def test_control_solve_anytime_is_not_correct():
    cell = small("vc_c125.solve")
    cell.config = control.control_config(cell.config, rounds=2,
                                         node_budget=0)
    result = run(cell)
    assert not result["correct"]
    assert result["checks"]["unfinished"]["value"] > 0


def test_control_service_node_budget_is_not_correct():
    cell = small("open")
    cell.config = control.control_config(cell.config, rounds=0,
                                         node_budget=20)
    result = run(cell)
    assert not result["correct"]
    assert result["checks"]["unfinished"]["value"] > 0
