"""Compile rehearsals for the TPU v5e, made without a chip.

The TPU compiler is installed with jax and compiles for a chip that is
described rather than attached (``v5e:2x2``).  These tests pass shapes
only — nothing runs — and prove what interpret mode cannot: that every
surviving bitset-kernel layout lowers through Mosaic at real widths
(n = 300, L = 1024, K = 8) and that whole engine rounds under
``backend="pallas"`` carry the compiled kernel (``tpu_custom_call``), on
one chip and sharded over the 4-chip mesh.

The topology is described inside a module-scoped fixture (never at
import), which skips where it cannot be described; the persistent
compilation cache is off around the compiles, since an entry written for
a described chip cannot be read back without one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import registry
from repro.core.distributed import (lane_partition_specs,
                                    make_distributed_round, make_round)
from repro.core.engine import init_lanes
from repro.kernels import autotune, bitset_ops
from repro.problems.dominating_set import make_dominating_set
from repro.problems.graphs import num_words
from repro.problems.vertex_cover import make_vertex_cover
from repro.service.batch_problem import StackedSpec, StackedTables
from repro.service.driver import make_service_round_fns

N, LANES, SLOTS, STEPS = 300, 1024, 8, 16
W = num_words(N)
KERNEL_MARK = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:         # no TPU compiler here: skip, loudly
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return jax.sharding.Mesh(np.array(topo.devices), ("workers",))


@pytest.fixture
def on_tpu(topo, monkeypatch):
    """Steer the code that asks the host platform: kernels compile (no
    interpreter) and the autotuner reads the described chip's entry."""
    monkeypatch.setattr(autotune, "resolve_interpret",
                        lambda interpret: bool(interpret))
    monkeypatch.setattr(autotune, "_device_kind",
                        lambda: topo.devices[0].device_kind)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda l: _spec(l.shape, l.dtype, sharding), tree)


def _tpu_tile(stages, *, k, stacked):
    """The autotuner's best tile for ``stages`` under the v5e model."""
    costs = [(autotune.predict_cost(N, W, LANES, k, tile=t, stages=stages,
                                    platform="tpu", stacked=stacked), t)
             for t in autotune.candidate_tiles(N)]
    return min((c, t) for c, t in costs if c is not None)[1]


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("stages", [1, 2])
def test_count_stats_compiles(one_chip, on_tpu, stages):
    tile = _tpu_tile(stages, k=1, stacked=False)
    compiled = _compile(
        lambda t, m, v: bitset_ops.count_stats(t, m, v, tile=tile,
                                               stages=stages,
                                               interpret=False),
        _spec((N, W), jnp.uint32, one_chip),
        _spec((LANES, W), jnp.uint32, one_chip),
        _spec((LANES, W), jnp.uint32, one_chip))
    assert KERNEL_MARK in compiled.as_text()


@pytest.mark.parametrize("stages", [1, 2])
def test_stacked_count_stats_compiles(one_chip, on_tpu, stages):
    tile = _tpu_tile(stages, k=SLOTS, stacked=True)
    compiled = _compile(
        lambda t, i, m, v: bitset_ops.stacked_count_stats(
            t, i, m, v, tile=tile, stages=stages, interpret=False),
        _spec((SLOTS, N, W), jnp.uint32, one_chip),
        _spec((LANES,), jnp.int32, one_chip),
        _spec((LANES, W), jnp.uint32, one_chip),
        _spec((LANES, W), jnp.uint32, one_chip))
    assert KERNEL_MARK in compiled.as_text()


def test_autotuner_picks_feasible_choice_on_v5e(on_tpu):
    autotune.clear_cache()
    try:
        for k, stacked in ((1, False), (SLOTS, True)):
            c = autotune.choose(N, W, lanes=LANES, k=k, platform="tpu",
                                stacked=stacked)
            assert autotune.predict_cost(N, W, LANES, k, tile=c.tile,
                                         stages=c.stages, platform="tpu",
                                         stacked=stacked) is not None
    finally:
        autotune.clear_cache()


def _stacked_tables(sharding):
    return StackedTables(_spec((SLOTS, N, W), jnp.uint32, sharding),
                         _spec((SLOTS, W), jnp.uint32, sharding),
                         _spec((SLOTS,), jnp.int32, sharding))


def _service_lanes(spec):
    proto = spec.bind(StackedTables(*(jnp.asarray(t)
                                      for t in spec.empty_tables())))
    return jax.eval_shape(lambda: init_lanes(proto, LANES, seed_root=False,
                                             bind_instance=False))


@pytest.mark.parametrize("family", ["vc", "ds"])
def test_solve_round_compiles_with_kernel(one_chip, on_tpu, family):
    maker, inst = {"vc": (make_vertex_cover, "cell60"),
                   "ds": (make_dominating_set, "gnp:64:20:1")}[family]
    prob = maker(registry.get(family).parse(inst), backend="pallas",
                 interpret=False)
    lanes = jax.eval_shape(lambda: init_lanes(prob, LANES))
    compiled = _compile(make_round(prob, STEPS), _shapes(lanes, one_chip))
    assert KERNEL_MARK in compiled.as_text()


def test_service_round_and_rebuild_compile_with_kernel(one_chip, on_tpu):
    spec = StackedSpec(n=N, k=SLOTS)
    round_fn, rebuild = make_service_round_fns(spec, "pallas", STEPS)
    lanes = _shapes(_service_lanes(spec), one_chip)
    tables = _stacked_tables(one_chip)
    for fn in (round_fn, rebuild):
        compiled = fn.lower(lanes, tables).compile()
        assert KERNEL_MARK in compiled.as_text()


def test_mesh_solve_round_compiles(mesh4, on_tpu):
    prob = make_vertex_cover(registry.get("vc").parse("gnp:100:10:1"),
                             backend="pallas", interpret=False)
    specs = lane_partition_specs(prob, mesh4.axis_names)
    lanes = jax.eval_shape(lambda: init_lanes(prob, LANES))
    args = jax.tree_util.tree_map(
        lambda l, s: _spec(l.shape, l.dtype, NamedSharding(mesh4, s)),
        lanes, specs)
    compiled = make_distributed_round(prob, mesh4, STEPS).lower(
        args).compile()
    text = compiled.as_text()
    assert KERNEL_MARK in text and "all-gather" in text


def test_mesh_service_round_and_rebuild_compile(mesh4, on_tpu):
    spec = StackedSpec(n=N, k=SLOTS)
    round_fn, rebuild = make_service_round_fns(spec, "pallas", STEPS,
                                               mesh=mesh4)
    specs = lane_partition_specs(
        spec.bind(StackedTables(*(jnp.asarray(t)
                                  for t in spec.empty_tables()))),
        mesh4.axis_names)
    lanes = jax.tree_util.tree_map(
        lambda l, s: _spec(l.shape, l.dtype, NamedSharding(mesh4, s)),
        _service_lanes(spec), specs)
    tables = _stacked_tables(NamedSharding(mesh4, P()))
    for fn in (round_fn, rebuild):
        assert KERNEL_MARK in fn.lower(lanes, tables).compile().as_text()

