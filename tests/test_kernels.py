"""Pallas kernel validation: interpret=True vs pure-jnp oracles.

Each kernel is swept over shapes and compared exactly against its ref.py
oracle.  The kernel layer also depends on nothing above it: importing
``repro.kernels.ops`` loads no model, training or decoding code.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.bitset_degree import degree_argmax, degree_stats
from repro.problems.graphs import gnp_graph


# ---------------------------------------------------------------------------
# bitset degree/argmax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,p,lanes,tile", [
    (60, 0.2, 4, 32), (200, 0.1, 8, 128), (300, 0.05, 2, 128),
    (128, 0.5, 16, 64),
])
def test_degree_argmax_matches_ref(n, p, lanes, tile):
    g = gnp_graph(n, p, seed=n)
    adj = jnp.asarray(g.adj)
    key = jax.random.PRNGKey(n)
    alive = jax.random.bernoulli(key, 0.7, (lanes, n))
    # pack alive masks
    w = adj.shape[1]
    masks = np.zeros((lanes, w), np.uint32)
    av = np.asarray(alive)
    for l in range(lanes):
        for v in range(n):
            if av[l, v]:
                masks[l, v // 32] |= np.uint32(1) << np.uint32(v % 32)
    masks = jnp.asarray(masks)
    got = degree_argmax(adj, masks, tile=tile, interpret=True)
    want = ref.degree_argmax_ref(adj, masks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_degree_argmax_all_dead():
    g = gnp_graph(40, 0.3, seed=1)
    adj = jnp.asarray(g.adj)
    masks = jnp.zeros((3, adj.shape[1]), jnp.uint32)
    got = degree_argmax(adj, masks, interpret=True)
    assert (np.asarray(got)[:, 0] == -1).all()


def test_degree_argmax_tie_break_smallest_id():
    """4-regular circulant: every vertex ties; the pick must be vertex 0."""
    from repro.problems.graphs import circulant_graph, full_mask
    g = circulant_graph(96, (1, 7))
    adj = jnp.asarray(g.adj)
    alive = jnp.asarray(full_mask(g.n))[None, :]
    got = degree_argmax(adj, alive, tile=32, interpret=True)
    assert got[0, 0] == 4 and got[0, 1] == 0


@pytest.mark.parametrize("n,p,lanes,tile", [
    (60, 0.2, 4, 32), (200, 0.1, 8, 128), (300, 0.05, 2, 128),
    (128, 0.5, 16, 64),
])
def test_degree_stats_matches_ref(n, p, lanes, tile):
    """The fused (degree, argmax, degree-sum) triple behind vertex cover's
    single-pass evaluate (DESIGN.md §3) — exact match vs the jnp oracle."""
    g = gnp_graph(n, p, seed=n + 1)
    adj = jnp.asarray(g.adj)
    alive = jax.random.bernoulli(jax.random.PRNGKey(n + 1), 0.6, (lanes, n))
    w = adj.shape[1]
    masks = np.zeros((lanes, w), np.uint32)
    av = np.asarray(alive)
    for l in range(lanes):
        for v in range(n):
            if av[l, v]:
                masks[l, v // 32] |= np.uint32(1) << np.uint32(v % 32)
    masks = jnp.asarray(masks)
    got = degree_stats(adj, masks, tile=tile, interpret=True)
    want = ref.degree_stats_ref(adj, masks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_degree_stats_all_dead_and_vmap():
    g = gnp_graph(40, 0.3, seed=2)
    adj = jnp.asarray(g.adj)
    masks = jnp.zeros((3, adj.shape[1]), jnp.uint32)
    got = np.asarray(degree_stats(adj, masks, interpret=True))
    np.testing.assert_array_equal(got, np.full((3, 3), [-1, -1, 0]))
    # vmap over lane masks (as the engine does) must match the flat call.
    from repro.problems.graphs import full_mask
    alive = jnp.tile(jnp.asarray(full_mask(g.n))[None, :], (4, 1))
    flat = degree_stats(adj, alive, tile=32, interpret=True)
    mapped = jax.vmap(
        lambda m: degree_stats(adj, m[None, :], tile=32, interpret=True)[0]
    )(alive)
    np.testing.assert_array_equal(np.asarray(mapped), np.asarray(flat))


# ---------------------------------------------------------------------------
# layering
# ---------------------------------------------------------------------------

_IMPORT_PROBE = """
import importlib.util, json, sys
import repro.kernels.ops, repro.kernels.ref
gone = tuple("repro." + name for name in ("models", "train", "serve"))
print(json.dumps({
    "loaded": sorted(m for m in sys.modules
                     if any(m == g or m.startswith(g + ".") for g in gone)),
    "importable": [g for g in gone if importlib.util.find_spec(g)],
}))
"""


def test_kernel_layer_imports_nothing_above_it():
    """A fresh interpreter imports the dispatchers and their oracles and
    loads no module of a language-model stack; no such package exists."""
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _IMPORT_PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"loaded": [], "importable": []}
