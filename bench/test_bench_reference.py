"""The plain reference and the comparison that decides ``correct``."""

import itertools

import numpy as np
import pytest

import judge
import plain_ref
from gnp import gnp_dense, relabel


def brute(family, dense):
    n = dense.shape[0]
    for size in range(n + 1):
        for subset in itertools.combinations(range(n), size):
            chosen = np.zeros(n, bool)
            chosen[list(subset)] = True
            if plain_ref.is_solution(family, dense, chosen):
                return size


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("family", ["vc", "ds"])
def test_reference_optimum_matches_brute_force(family, seed):
    dense = gnp_dense(11, 0.3, seed)
    assert plain_ref.OPTIMUM[family](dense) == brute(family, dense)


def test_reference_knows_the_c125_panel_instance():
    # G(125, 0.10) from seed 1: optimum 91 (checked by an independent ILP).
    assert plain_ref.min_vertex_cover(gnp_dense(125, 0.10, 1)) == 91


def pack(chosen):
    words = np.zeros((len(chosen) + 31) // 32, np.uint32)
    for v in np.flatnonzero(chosen):
        words[v // 32] |= np.uint32(1) << np.uint32(v % 32)
    return words


def cover_of(dense):
    """A minimum vertex cover by brute force (small graphs only)."""
    n = dense.shape[0]
    for size in range(n + 1):
        for subset in itertools.combinations(range(n), size):
            chosen = np.zeros(n, bool)
            chosen[list(subset)] = True
            if plain_ref.is_solution("vc", dense, chosen):
                return chosen


def test_compare_counts_each_kind_of_fault():
    dense = gnp_dense(10, 0.3, 2)
    best = cover_of(dense)
    value = int(best.sum())
    other = relabel(dense, np.roll(np.arange(10), 3))
    good = judge.Answer(0, "vc", dense, dense, "done", value, pack(best))
    answers = [
        good,
        good._replace(status="missing", value=None, payload=None),
        good._replace(status="unproven"),
        good._replace(value=value + 1),
        good._replace(payload=pack(np.roll(best, 1))),   # not a cover
        good._replace(dense=other),                      # another graph's
    ]
    v = judge.compare(answers)
    assert v.numbers == {"missing": 1, "unfinished": 1, "wrong_optimum": 1,
                         "bad_solution": 2}
    assert (v.correct, v.attempted, v.failed) == (False, 6, 5)
    assert judge.compare([good, good]).correct
    assert not judge.compare([]).correct
