"""The instances and arrivals are a function of the seed alone."""

from types import SimpleNamespace

import numpy as np
import pytest

import harness

SOLVE = harness.Cell(harness.load_json(harness.ROOT / "BENCHMARK.json"),
                     "vc_c125.solve")
# Service traffic (the ``service`` generator, which no cell of
# BENCHMARK.json runs yet): open-loop arrivals over a mixed pool.
R80 = SimpleNamespace(
    config={"requests": [{"family": "vc", "p": 0.10, "n": [50, 80],
                          "share": 0.5},
                         {"family": "ds", "p": 0.10, "n": [30, 50],
                          "share": 0.5}]},
    mix={"kind": "service", "pool_seed": 1, "pool_size": 64,
         "arrivals": {"process": "poisson", "rate": 28.0}})
SEED = 2**31 + 977


def make(cell, seed):
    return harness.generator(cell.mix["kind"]).make(cell.config, cell.mix,
                                                    seed)


def test_gnp_draw_matches_the_classic_generator():
    from gnp import gnp_dense
    rng = np.random.RandomState(5)
    upper = np.triu(rng.rand(30, 30) < 0.2, k=1)
    assert np.array_equal(gnp_dense(30, 0.2, 5), upper | upper.T)


def test_panel_is_the_same_instances_for_every_seed_in_a_seeded_order():
    a, b, c = make(SOLVE, SEED), make(SOLVE, SEED), make(SOLVE, SEED + 1)
    for x, y in zip(a.dense, c.dense):
        assert np.array_equal(x, y)
    assert all(np.array_equal(a.order(p), b.order(p)) for p in range(5))
    assert sorted(a.order(0)) == list(range(len(a.dense)))
    assert any(not np.array_equal(a.order(p), c.order(p)) for p in range(5))
    assert a.dense[0].shape == (125, 125)


def test_requests_and_arrivals_repeat_for_one_seed():
    a, b = make(R80, SEED), make(R80, SEED)
    for j in range(0, 200, 7):
        ra, rb = a.request(j), b.request(j)
        assert (ra.pool, ra.family) == (rb.pool, rb.family)
        assert np.array_equal(ra.dense, rb.dense)
    assert np.array_equal(a.due_times(30), b.due_times(30))


def test_every_seed_offers_the_same_load_in_another_order():
    a, c = make(R80, SEED), make(R80, SEED + 1)
    due_a, due_c = a.due_times(30), c.due_times(30)
    rate = R80.mix["arrivals"]["rate"]
    assert len(due_a) == len(due_c) == int(rate * 30)
    assert np.allclose(np.sort(np.diff(due_a, prepend=0)),
                       np.sort(np.diff(due_c, prepend=0)))
    assert not np.array_equal(due_a, due_c)
    assert 0 < due_a[0] and due_a[-1] < 30
    assert due_a[-1] == pytest.approx(due_c[-1])
    pool = len(a.pool)
    assert sorted(a.request(j).pool for j in range(pool)) == list(range(pool))


def test_a_request_is_a_relabelling_of_its_pool_entry():
    a = make(R80, SEED)
    r = a.request(3)
    base = a.pool[r.pool].dense
    assert r.dense.shape == base.shape
    assert sorted(r.dense.sum(axis=1)) == sorted(base.sum(axis=1))
    families = {e.family for e in a.pool}
    assert families == {"vc", "ds"}
    vc = sorted(e.n for e in a.pool if e.family == "vc")
    assert vc[0] == 50 and vc[-1] == 80
