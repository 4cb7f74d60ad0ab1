"""The service gives the same answers as the serial oracle.

A seeded mix of vertex cover and dominating set requests goes to
``Solver(...).serve()`` under a sweep of slot and lane counts.  Every
ticket must resolve "done" with the oracle's optimum and a payload that is
a feasible solution of that size.
"""

import random

import pytest

from _solutions import assert_feasible
from repro import registry
from repro.problems import gnp_graph
from repro.service import SolveRequest
from repro.solver import Solver, SolverConfig

MAX_N = 16
REQUESTS = 4


def request_mix(seed: int):
    """``REQUESTS`` seeded (family, graph) pairs, vc and ds in turn."""
    rng = random.Random(seed)
    return [(("vc", "ds")[i % 2],
             gnp_graph(rng.randrange(12, MAX_N + 1),
                       rng.choice((0.2, 0.3)), seed=rng.randrange(10 ** 6)))
            for i in range(REQUESTS)]


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("lanes", [8, 32])
@pytest.mark.parametrize("slots", [1, 2, 4])
def test_service_matches_oracle(slots, lanes, seed):
    mix = request_mix(seed)
    svc = Solver(SolverConfig(lanes=lanes, steps_per_round=16)).serve(
        max_n=MAX_N, slots=slots)
    tickets = [svc.submit(SolveRequest(rid=i, graph=g, family=fam))
               for i, (fam, g) in enumerate(mix)]
    for ticket, (fam, g) in zip(tickets, mix):
        want = Solver().oracle(registry.problem(fam, g)).best
        res = ticket.result()
        assert res.status == "done"
        assert res.optimum == want
        assert_feasible(fam, g, res.payload, want)
