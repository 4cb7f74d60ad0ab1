"""Every registered family: ``Solver.solve`` agrees with ``Solver.oracle``.

For each family in ``registry.names()``, four seeded instances are solved
under a sweep of lane counts, round lengths and fused steps.  Each solve
must find the serial oracle's optimum, return a payload that is a feasible
solution of that value, and keep the steal counts in the paper's relation
``T_S <= T_R + 1``.
"""

import pytest

from _solutions import assert_feasible
from repro import registry
from repro.problems import gnp_graph, random_regularish_graph
from repro.solver import Solver, SolverConfig

SEEDS = range(4)

#: One seeded instance maker per registered family.  The sizes keep the
#: serial oracle well under a second; vertex cover takes 4-regular graphs,
#: whose search trees are larger than G(n, p)'s at this size.
INSTANCES = {
    "vc": lambda seed: random_regularish_graph(16 + 2 * seed, 4, seed=seed),
    "ds": lambda seed: gnp_graph(16 + 2 * seed, 0.2, seed=100 + seed),
    "ss": lambda seed: f"ss:16:{seed}",
}

#: (lanes, steps_per_round): one lane stepping once a round, a small pool,
#: and a wide pool whose rounds are long.
POOLS = [(1, 1), (8, 8), (32, 64)]


def test_every_registered_family_is_swept():
    assert set(registry.names()) == set(INSTANCES)


@pytest.mark.parametrize("fused_steps", [1, 2])
@pytest.mark.parametrize("lanes,steps_per_round", POOLS)
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("family", sorted(INSTANCES))
def test_solve_matches_oracle(family, seed, lanes, steps_per_round,
                              fused_steps):
    handle = registry.problem(family, INSTANCES[family](seed))
    want = Solver().oracle(handle)
    res = Solver(SolverConfig(lanes=lanes, steps_per_round=steps_per_round,
                              fused_steps=fused_steps)).solve(handle)
    assert res.stats.best == want.best
    assert_feasible(family, handle.instance, res.payload, want.best)
    assert res.stats.nodes > 0
    assert res.stats.t_s <= res.stats.t_r + 1      # paper: T_S <= T_R
