"""Split-phase reduction, block autotuning, and the fused multi-step
round (DESIGN.md §5.5/§5.6 — the ISSUE 6 tentpole).

Four properties:

1. SPLIT-PHASE PARITY — the stages=2 kernels (stage-1 per-block partial
   stats + stage-2 combine) are bitwise-identical to the stages=1 grid,
   the ``ref.py`` jnp oracle and an independent numpy oracle across a
   (n, lanes, tile) sweep — including the smallest-id tie-break when the
   winning count appears in several tile blocks, and under vmap lifting.
2. IDLE-LANE PARKING — ``stacked_count_stats`` lanes with inst < 0
   (NO_INSTANCE) produce the empty-pass row (-1, -1, 0, 0) and are
   unaffected by any slot's table contents.
3. AUTOTUNER — ``kernels.autotune.choose`` returns valid cached choices
   (power-of-two tile, stages ∈ {1, 2}) and ``tile``/``stages``
   validation rejects malformed values with clear errors.
4. FUSED ROUNDS — ``evaluate_batch`` is bitwise-identical to
   ``vmap(evaluate)`` for vc, ds and stacked-service states, and the
   engine's search tree is identical to the serial oracle for S ∈ {1, 4}
   fused steps under both backends and autotuned tiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import INF_VALUE
from repro.core.engine import NO_INSTANCE, init_lanes, make_expand
from repro.core.serial import serial_rb
from repro.kernels import autotune, bitset_ops, ref
from repro.problems.dominating_set import (make_dominating_set,
                                           make_dominating_set_py)
from repro.problems.graphs import circulant_graph, full_mask, gnp_graph
from repro.problems.vertex_cover import (make_vertex_cover,
                                         make_vertex_cover_py)
from repro.service.batch_problem import (FAMILY_DS, FAMILY_VC, StackedSpec,
                                         StackedTables, pack_instance)
from repro.solver import Solver, SolverConfig
from test_bitset_ops import np_count_stats, random_masks


# -- 1. split-phase parity ----------------------------------------------------


@pytest.mark.parametrize("n,lanes,tile", [
    (40, 4, 8), (96, 6, 16), (130, 8, 32), (200, 5, 64), (64, 3, 64),
])
def test_split_phase_matches_single_stage_and_oracles(n, lanes, tile):
    """stages=2 ≡ stages=1 ≡ ref.py ≡ numpy across block counts (the
    tile sweep covers blocks ∈ {1 .. 17})."""
    g = gnp_graph(n, 0.2, seed=n)
    rng = np.random.default_rng(n)
    mask, valid = random_masks(rng, lanes, n), random_masks(rng, lanes, n)
    adj = jnp.asarray(g.adj)
    want = np_count_stats(g.adj, mask, valid)
    split = bitset_ops.count_stats(adj, jnp.asarray(mask),
                                   jnp.asarray(valid), tile=tile, stages=2)
    seq = bitset_ops.count_stats(adj, jnp.asarray(mask),
                                 jnp.asarray(valid), tile=tile, stages=1)
    np.testing.assert_array_equal(np.asarray(split), want)
    np.testing.assert_array_equal(np.asarray(seq), want)
    np.testing.assert_array_equal(
        np.asarray(ref.count_stats_ref(adj, jnp.asarray(mask),
                                       jnp.asarray(valid))), want)


def test_split_phase_tiebreak_across_block_boundary():
    """The winning count appears in EVERY tile block (circulant graph:
    all vertices tie) — the combine must keep the smallest id, i.e. the
    winner of block 0, not of the last block written."""
    g = circulant_graph(96, (1, 7))            # 4-regular: global tie
    adj = jnp.asarray(g.adj)
    alive = jnp.asarray(full_mask(g.n))[None, :]
    for tile in (8, 16, 32):                   # 12, 6, 3 blocks
        got = np.asarray(bitset_ops.count_stats(adj, alive, alive,
                                                tile=tile, stages=2))[0]
        assert (got[0], got[1]) == (4, 0), f"tile={tile}: {got}"
    # Tie constructed to straddle exactly one block boundary: only
    # vertices 15 and 16 valid (tile=16 puts them in blocks 0 and 1).
    sel = np.zeros((1, g.words), np.uint32)
    for v in (15, 16):
        sel[0, v // 32] |= np.uint32(1) << np.uint32(v % 32)
    got = np.asarray(bitset_ops.count_stats(
        adj, alive, jnp.asarray(sel), tile=16, stages=2))[0]
    assert got[1] == 15                        # smaller id wins the tie


def test_split_phase_vmap_lift():
    """vmap over lanes — the engine's calling convention — agrees with
    the flat call for the split-phase path."""
    g = gnp_graph(80, 0.25, seed=17)
    rng = np.random.default_rng(17)
    mask = jnp.asarray(random_masks(rng, 6, g.n))
    valid = jnp.asarray(random_masks(rng, 6, g.n))
    adj = jnp.asarray(g.adj)
    flat = bitset_ops.count_stats(adj, mask, valid, tile=16, stages=2)
    mapped = jax.jit(jax.vmap(
        lambda m, v: bitset_ops.count_stats(adj, m[None, :], v[None, :],
                                            tile=16, stages=2)[0]))(
        mask, valid)
    np.testing.assert_array_equal(np.asarray(mapped), np.asarray(flat))


@pytest.mark.parametrize("stages", [1, 2])
def test_stacked_split_phase_matches_numpy(stages):
    k, n, lanes = 3, 40, 9
    w = (n + 31) // 32
    tables = np.zeros((k, n, w), np.uint32)
    for i, s in enumerate((21, 22, 23)):
        g = gnp_graph(n - 2 * i, 0.3, seed=s)
        tables[i] = pack_instance(g, i % 2, n)[0]
    rng = np.random.default_rng(31)
    inst = rng.integers(-1, k, lanes).astype(np.int32)
    inst[0] = -1                               # force an idle lane
    mask, valid = random_masks(rng, lanes, n), random_masks(rng, lanes, n)
    got = bitset_ops.stacked_count_stats(
        jnp.asarray(tables), jnp.asarray(inst), jnp.asarray(mask),
        jnp.asarray(valid), tile=16, stages=stages)
    want = np.stack([
        np.array([-1, -1, 0, 0], np.int32) if int(i) < 0
        else np_count_stats(tables[int(i)], mask[l:l + 1],
                            valid[l:l + 1])[0]
        for l, i in enumerate(inst)])
    np.testing.assert_array_equal(np.asarray(got), want)


# -- 2. idle-lane parking -----------------------------------------------------


def test_stacked_idle_lanes_ignore_table_contents():
    """A NO_INSTANCE lane's output is the empty-pass row and does not
    change when every slot's table flips every bit — idle lanes do no
    table traffic."""
    k, n, lanes = 2, 32, 5
    w = (n + 31) // 32
    rng = np.random.default_rng(5)
    tables = rng.integers(0, 2**32, (k, n, w),
                          dtype=np.uint64).astype(np.uint32)
    inst = np.full(lanes, NO_INSTANCE, np.int32)
    mask, valid = random_masks(rng, lanes, n), random_masks(rng, lanes, n)

    def run(tb):
        return np.asarray(bitset_ops.stacked_count_stats(
            jnp.asarray(tb), jnp.asarray(inst), jnp.asarray(mask),
            jnp.asarray(valid), tile=16))

    parked = np.tile(np.array([-1, -1, 0, 0], np.int32), (lanes, 1))
    np.testing.assert_array_equal(run(tables), parked)
    np.testing.assert_array_equal(run(~tables), parked)


# -- 3. autotuner + validation ------------------------------------------------


def test_autotune_choices_are_valid_and_cached():
    autotune.clear_cache()
    for (n, w, lanes, k) in [(60, 2, 16, 1), (128, 4, 64, 1),
                             (256, 8, 64, 8), (7, 1, 1, 1)]:
        c = autotune.choose(n, w, lanes=lanes, k=k)
        assert c.tile >= 1 and (c.tile & (c.tile - 1)) == 0, c
        assert c.stages in (1, 2), c
        assert autotune.choose(n, w, lanes=lanes, k=k) is c  # cache hit
    # The predicted cost of the chosen config is minimal among candidates.
    c = autotune.choose(128, 4, lanes=64)
    best = autotune.predict_cost(128, 4, 64, 1, tile=c.tile,
                                 stages=c.stages, platform="cpu")
    for tile in autotune.candidate_tiles(128):
        for stages in (1, 2):
            cost = autotune.predict_cost(128, 4, 64, 1, tile=tile,
                                         stages=stages, platform="cpu")
            if cost is not None:
                assert best <= cost + 1e-12


def test_autotune_reads_peak_table_on_tpu(monkeypatch):
    """On a TPU the cost model reads the chip's peak-table entry; a
    device kind missing from the table is an error, not a default."""
    from repro import peaks
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.int8_ops, v5e.hbm_bytes_per_s,
            v5e.hbm_bytes) == (197e12, 393e12, 819e9, 16e9)
    autotune.clear_cache()
    try:
        monkeypatch.setattr(autotune, "_device_kind", lambda: "TPU v5 lite")
        c = autotune.choose(300, 10, lanes=1024, platform="tpu")
        assert autotune.predict_cost(300, 10, 1024, 1, tile=c.tile,
                                     stages=c.stages,
                                     platform="tpu") is not None
        monkeypatch.setattr(autotune, "_device_kind", lambda: "TPU v0 x")
        with pytest.raises(ValueError, match="peak-table"):
            autotune.choose(300, 10, lanes=1024, platform="tpu")
    finally:
        autotune.clear_cache()


def test_interpret_mode_is_cpu_only(monkeypatch):
    assert autotune.resolve_interpret(True) is True
    assert autotune.resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert autotune.resolve_interpret(None) is True
    for platform in ("tpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda p=platform: p)
        assert autotune.resolve_interpret(None) is False


def test_tile_validation_errors():
    g = gnp_graph(40, 0.2, seed=1)
    adj = jnp.asarray(g.adj)
    m = jnp.asarray(random_masks(np.random.default_rng(1), 2, g.n))
    # Split-phase requires a power-of-two tile; stages=1 does not.
    with pytest.raises(ValueError, match="power of two"):
        bitset_ops.count_stats(adj, m, m, tile=24, stages=2)
    np.testing.assert_array_equal(
        np.asarray(bitset_ops.count_stats(adj, m, m, tile=24, stages=1)),
        np_count_stats(g.adj, np.asarray(m), np.asarray(m)))
    for bad in (0, -4, True):
        with pytest.raises(ValueError, match="tile"):
            bitset_ops.count_stats(adj, m, m, tile=bad)
    with pytest.raises(ValueError, match="stages"):
        bitset_ops.count_stats(adj, m, m, tile=16, stages=3)
    with pytest.raises(ValueError, match="multiple of 8"):
        bitset_ops.count_stats(adj, m, m, tile=12, stages=1)


# -- 4. fused rounds ----------------------------------------------------------


def _tree_equal(a, b):
    fa, _ = jax.tree_util.tree_flatten(a)
    fb, _ = jax.tree_util.tree_flatten(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(fa, fb))


@pytest.mark.parametrize("family", ["vc", "ds"])
def test_evaluate_batch_matches_vmap_evaluate(family):
    g = gnp_graph(48, 0.2, seed=13)
    maker = make_vertex_cover if family == "vc" else make_dominating_set
    prob = maker(g, backend="pallas")
    assert prob.evaluate_batch is not None
    lanes = 7
    rng = np.random.default_rng(13)
    root = prob.root()
    states = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (lanes,) + x.shape), root)
    leaves, treedef = jax.tree_util.tree_flatten(states)
    sub = jnp.asarray(random_masks(rng, lanes, g.n))
    leaves = [leaves[0] & sub] + list(leaves[1:])
    states = jax.tree_util.tree_unflatten(treedef, leaves)
    best = jnp.full((lanes,), int(INF_VALUE), jnp.int32)
    assert _tree_equal(jax.jit(prob.evaluate_batch)(states, best),
                       jax.jit(jax.vmap(prob.evaluate))(states, best))


def test_stacked_evaluate_batch_matches_vmap_evaluate():
    spec = StackedSpec(n=40, k=3)
    tb = spec.empty_tables()
    for s, (fam, seed) in enumerate([(FAMILY_VC, 41), (FAMILY_DS, 42),
                                     (FAMILY_VC, 43)]):
        adj, fm, f = pack_instance(gnp_graph(40 - s, 0.25, seed=seed),
                                   fam, 40)
        tb.adj[s], tb.fullm[s], tb.family[s] = adj, fm, f
    tables = StackedTables(*(jnp.asarray(t) for t in tb))
    prob = spec.bind(tables, "pallas")
    assert prob.evaluate_batch is not None
    inst = jnp.asarray([0, 1, 2, 0, 1, -1, 2, -1], jnp.int32)
    states = jax.vmap(prob.instance_root)(inst)
    best = jnp.full((inst.shape[0],), int(INF_VALUE), jnp.int32)
    assert _tree_equal(jax.jit(prob.evaluate_batch)(states, best),
                       jax.jit(jax.vmap(prob.evaluate))(states, best))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fused_steps_tree_identity(backend):
    """S ∈ {1, 4} fused steps produce the IDENTICAL search (same best,
    same node count, same payload) for both backends at autotuned tiles,
    and the optimum matches the serial oracle."""
    g = gnp_graph(24, 0.3, seed=2)
    for maker, py in ((make_vertex_cover, make_vertex_cover_py),
                      (make_dominating_set, make_dominating_set_py)):
        prob = maker(g, backend=backend)
        want_best, _, _ = serial_rb(py(g))
        results = [
            Solver(SolverConfig(lanes=4, steps_per_round=8,
                                backend=backend, fused_steps=s)).solve(prob)
            for s in (1, 4)]
        for res in results:
            assert res.stats.best == want_best
        assert results[0].stats.nodes == results[1].stats.nodes
        assert np.array_equal(results[0].payload, results[1].payload)


@pytest.mark.parametrize("fused_steps", [1, 4])
def test_fused_steps_expand_identity(fused_steps):
    """make_expand at S>1 visits the identical node sequence (same nodes
    AND same per-lane step counters) as S=1."""
    g = gnp_graph(20, 0.3, seed=8)
    prob = make_vertex_cover(g)
    lanes0 = init_lanes(prob, 4)
    base = jax.jit(make_expand(prob, 16))(lanes0)
    fused = jax.jit(make_expand(prob, 16, fused_steps=fused_steps))(lanes0)
    assert _tree_equal(base, fused)
