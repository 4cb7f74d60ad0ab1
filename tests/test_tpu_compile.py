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

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import registry
from repro.core.distributed import (jit_round_loop, lane_partition_specs,
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


def test_cell_mesh_round_elects_the_cover_across_chips(mesh4):
    """The four-chip benchmark round (vc, G(150, 0.10), 768 lanes a chip,
    64 steps, the default backend) compiles for the described host, and
    the incumbent's cover (``u32[1, 5]``) is all-reduced under the
    ``round.share_best`` scope."""
    prob = make_vertex_cover(registry.get("vc").parse("gnp:150:10:1"))
    specs = lane_partition_specs(prob, mesh4.axis_names)
    lanes = jax.eval_shape(lambda: init_lanes(prob, 4 * 768))
    args = jax.tree_util.tree_map(
        lambda l, s: _spec(l.shape, l.dtype, NamedSharding(mesh4, s)),
        lanes, specs)
    text = make_distributed_round(prob, mesh4, 64).lower(
        args).compile().as_text()
    assert [line for line in text.splitlines()
            if re.search(r"= u32\[1,5\]\S* all-reduce(-start)?\(", line)
            and "round.share_best" in line]


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



def test_vc_round_replay_keeps_the_stack_in_place(one_chip):
    """The benchmark's round (vc, G(125, 0.10), 768 lanes, 64 steps): the
    steal's replay must not relayout the lane stack.  No copy makes a
    ``[768, 127, 4]`` stack leaf anywhere, and no loop body copies any
    ``[768, 127, ...]`` array; the copies that are left run once a round."""
    prob = make_vertex_cover(registry.get("vc").parse("gnp:125:10:1"))
    lanes = jax.eval_shape(lambda: init_lanes(prob, 768))
    text = _compile(make_round(prob, 64), _shapes(lanes, one_chip)).as_text()
    entry, comp, looped = None, None, []
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(2)
            entry = comp if head.group(1) else entry
        copy = re.search(r"%copy[.\d]* = \w+\[768,127(,\d+)*\]", line)
        if copy:
            assert "[768,127,4]" not in copy.group(0), line
            looped.append(comp)
    assert entry is not None
    assert all(c == entry for c in looped), looped


def _computations(text):
    """name -> instruction lines of each computation of an HLO module,
    and the entry computation's name."""
    comps, entry, comp = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(2)
            comps[comp] = []
            entry = comp if head.group(1) else entry
        elif comp is not None:
            comps[comp].append(line)
    return comps, entry


def _reachable(comps, roots):
    """The computations that ``roots`` call, directly or not."""
    seen, todo = set(), list(roots)
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            todo += re.findall(r"(?:body|condition|calls|to_apply)=%([\w.\-]+)",
                               line)
            for group in re.findall(
                    r"(?:branch_computations|called_computations)=\{([^}]*)\}",
                    line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def test_cell_mesh_loop_keeps_the_collectives_in_the_loop(mesh4):
    """The four-chip benchmark loop (vc, G(150, 0.10), 768 lanes a chip,
    64 steps) compiles for the described host as one outer loop of
    rounds: every all-gather (the steal) and all-reduce (the election,
    open work) of the round runs inside that loop's body."""
    prob = make_vertex_cover(registry.get("vc").parse("gnp:150:10:1"))
    specs = lane_partition_specs(prob, mesh4.axis_names)
    lanes = jax.eval_shape(lambda: init_lanes(prob, 4 * 768))
    args = jax.tree_util.tree_map(
        lambda l, s: _spec(l.shape, l.dtype, NamedSharding(mesh4, s)),
        lanes, specs)
    budget = _spec((), jnp.int32, NamedSharding(mesh4, P()))
    loop = jit_round_loop(make_round(prob, 64, mesh4.axis_names), prob, mesh4)
    text = loop.lower(args, budget).compile().as_text()
    assert re.match(r"HloModule jit_round_fn\b", text)
    comps, entry = _computations(text)
    bodies = [b for line in comps[entry] if " while(" in line
              for b in re.findall(r"body=%([\w.\-]+)", line)]
    assert len(bodies) == 1, bodies
    inside = _reachable(comps, bodies)
    found = collections.Counter(
        (m.group(1), comp in inside) for comp, lines in comps.items()
        for line in lines
        for m in [re.search(r"\b(all-gather|all-reduce)(?:-start)?\(", line)]
        if m)
    assert found[("all-gather", True)] >= 2
    assert found[("all-reduce", True)] >= 4
    assert not found[("all-gather", False)] and not found[("all-reduce", False)]


def test_vc_loop_keeps_the_stack_in_place(one_chip):
    """The one-chip benchmark loop (vc, G(125, 0.10), 768 lanes, 64
    steps): no copy makes a ``[768, 127, 4]`` stack leaf, in the loop or
    around it."""
    prob = make_vertex_cover(registry.get("vc").parse("gnp:125:10:1"))
    lanes = jax.eval_shape(lambda: init_lanes(prob, 768))
    text = jit_round_loop(make_round(prob, 64), prob).lower(
        _shapes(lanes, one_chip), _spec((), jnp.int32, one_chip)
    ).compile().as_text()
    assert re.match(r"HloModule jit_round_fn\b", text)
    assert " while(" in text
    assert not re.search(r"%copy[.\d]* = \w+\[768,127,4\]", text)
