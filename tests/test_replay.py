"""CONVERTINDEX replay over a block of lanes (``engine.replay_lanes``).

The batched replay is checked against a plain reference that uses only
the problem's own ``root_of`` and ``apply``: for each receiving lane, a
Python loop applies ``clip(bits[j], 0, 1)`` from the root of the lane's
instance and records every state on the way.  Every other slot must come
back bitwise untouched, so the stacks handed in are random words.

The round-level case runs ``make_round`` twice: as it is, and with the
replay swapped for the per-lane ``fori_loop`` replay the engine used
before (kept here, verbatim, as the oracle).  The lanes must match bit
for bit after every round.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import checkpoint as ckpt
from repro.core import steal
from repro.core.api import DELEGATED, LEFT, RIGHT, UNVISITED, root_of
from repro.core.distributed import make_round
from repro.core.engine import init_lanes, replay_lanes
from repro.problems import (gnp_graph, make_dominating_set,
                            make_subset_sum, make_vertex_cover)
from repro.service.batch_problem import StackedSpec, pack_instance

W = 8


def _stacked():
    spec = StackedSpec(n=14, k=3)
    tables = spec.empty_tables()
    mix = [("vc", gnp_graph(14, 0.3, seed=7)), ("ds", gnp_graph(12, 0.3, seed=9)),
           ("vc", gnp_graph(10, 0.4, seed=1))]
    for slot, (fam, g) in enumerate(mix):
        adj, fm, f = pack_instance(g, 0 if fam == "vc" else 1, spec.n)
        tables.adj[slot], tables.fullm[slot] = adj, fm
        tables.family[slot] = f
    return spec.bind(type(tables)(*(jnp.asarray(t) for t in tables)))


PROBLEMS = {
    "vc": lambda: make_vertex_cover(gnp_graph(16, 0.3, seed=3)),
    "ds": lambda: make_dominating_set(gnp_graph(12, 0.3, seed=5)),
    "subset_sum": lambda: make_subset_sum([3, 5, 7, 11, 13, 17, 19, 23,
                                           29, 31], 60),
    "stacked": _stacked,
}


@functools.lru_cache(maxsize=None)
def _problem(name):
    return PROBLEMS[name]()


def _random_stack(problem, rng):
    """Lane stacks full of random words, so an untouched slot shows."""
    proto = init_lanes(problem, W, seed_root=False).stack

    def noise(leaf):
        if leaf.dtype == jnp.bool_:
            return jnp.asarray(rng.integers(0, 2, leaf.shape).astype(bool))
        info = np.iinfo(leaf.dtype)
        return jnp.asarray(rng.integers(info.min, info.max, leaf.shape,
                                        dtype=np.int64).astype(leaf.dtype))

    return jax.tree_util.tree_map(noise, proto)


def _reference(problem, bits, depth, inst, recv, stack):
    """Plain replay: a Python loop of ``apply`` per receiving lane."""
    apply = jax.jit(problem.apply)
    out = jax.tree_util.tree_map(lambda s: np.array(s), stack)
    for lane in range(bits.shape[0]):
        if not recv[lane]:
            continue
        state = root_of(problem, jnp.int32(inst[lane]))
        path = [state]
        for j in range(int(depth[lane])):
            bit = jnp.int32(min(max(int(bits[lane, j]), 0), 1))
            state = apply(state, bit)
            path.append(state)
        for slot, st in enumerate(path):
            jax.tree_util.tree_map(
                lambda o, s: o.__setitem__((lane, slot), np.asarray(s)),
                out, st)
    return out


def _case(problem, case, rng):
    il = problem.max_depth + 1
    k = problem.num_instances
    bits = rng.choice(np.array([UNVISITED, DELEGATED, LEFT, RIGHT],
                               np.int8), size=(W, il))
    inst = rng.integers(0, k, W).astype(np.int32)
    if case == "no_receiver":
        recv = np.zeros(W, bool)
        depth = rng.integers(0, il, W).astype(np.int32)
    elif case == "depth_0":
        recv = np.ones(W, bool)
        depth = np.zeros(W, np.int32)
    elif case == "max_depth":
        recv = np.ones(W, bool)
        depth = np.full(W, problem.max_depth, np.int32)
    else:                       # some lanes receive, at mixed depths
        recv = np.arange(W) % 3 != 1
        depth = rng.integers(0, il, W).astype(np.int32)
        depth[0] = problem.max_depth
    return bits, depth, inst, recv


@pytest.mark.parametrize("case", ["no_receiver", "depth_0", "max_depth",
                                  "mixed"])
@pytest.mark.parametrize("name", sorted(PROBLEMS))
def test_replay_matches_plain_reference(name, case):
    problem = _problem(name)
    rng = np.random.default_rng([ord(c) for c in name + case])
    bits, depth, inst, recv = _case(problem, case, rng)
    stack = _random_stack(problem, rng)
    got = jax.jit(functools.partial(replay_lanes, problem))(
        jnp.asarray(bits), jnp.asarray(depth), jnp.asarray(inst),
        jnp.asarray(recv), stack)
    want = _reference(problem, bits, depth, inst, recv, stack)
    for g, w_ in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), w_)
    if case == "no_receiver":
        for g, s in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(stack)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(s))


@pytest.mark.parametrize("name", sorted(PROBLEMS))
def test_rebuild_stacks_on_mixed_depths(name):
    """A pool of lanes at mixed depths, some idle, with DELEGATED and
    UNVISITED marks on the path: active lanes get the stack of their
    current node, idle lanes keep theirs."""
    problem = _problem(name)
    rng = np.random.default_rng(len(name))
    bits, depth, inst, _ = _case(problem, "mixed", rng)
    active = np.arange(W) % 4 != 2
    lanes = init_lanes(problem, W, seed_root=False)._replace(
        idx=jnp.asarray(bits), depth=jnp.asarray(depth),
        inst=jnp.asarray(inst), active=jnp.asarray(active),
        stack=_random_stack(problem, rng))
    got = ckpt.rebuild_stacks(problem, lanes)
    want = _reference(problem, bits, depth, inst, active, lanes.stack)
    for g, w_ in zip(jax.tree_util.tree_leaves(got.stack),
                     jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), w_)


def replay_path(problem, bits, path_depth, stack, inst=jnp.int32(0)):
    """The engine's per-lane replay before the batched one, verbatim."""
    il = bits.shape[0]
    root = root_of(problem, inst)
    stack = jax.tree_util.tree_map(
        lambda s, r: jax.lax.dynamic_update_index_in_dim(s, r, 0, axis=0),
        stack, root)

    def body(j, carry):
        state, stack = carry
        bit = jnp.clip(bits[j].astype(jnp.int32), 0, 1)
        nxt = problem.apply(state, bit)
        take = j < path_depth
        state = jax.tree_util.tree_map(
            lambda a, b: jnp.where(take, b, a), state, nxt)
        stack = jax.tree_util.tree_map(
            lambda s, st: jax.lax.dynamic_update_index_in_dim(
                s, jnp.where(take, st,
                             jax.lax.dynamic_index_in_dim(s, jnp.clip(j + 1, 0, s.shape[0] - 1), keepdims=False)),
                jnp.clip(j + 1, 0, s.shape[0] - 1), axis=0),
            stack, state)
        return state, stack

    _, stack = jax.lax.fori_loop(0, il, body, (root, stack))
    return stack


def _vmapped_replay(problem, bits, depth, inst, recv, stack):
    """``install_tasks``' replay before the batched one: every lane
    replayed through all slots, the receivers' stacks kept."""
    new = jax.vmap(functools.partial(replay_path, problem))(
        bits, depth, stack, inst)
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(
            recv.reshape((-1,) + (1,) * (o.ndim - 1)), n, o), new, stack)


def test_round_matches_per_lane_replay(monkeypatch):
    """40 rounds of G(40, 0.2) vc at 64 lanes: the same lanes, round by
    round, with the batched replay and with the old per-lane replay."""
    problem = make_vertex_cover(gnp_graph(40, 0.2, seed=1))
    a = b = init_lanes(problem, 64)
    new_round = jax.jit(make_round(problem, 8)).lower(a).compile()
    with monkeypatch.context() as m:
        m.setattr(steal, "replay_lanes", _vmapped_replay)
        old_round = jax.jit(make_round(problem, 8)).lower(b).compile()
    received = 0
    for _ in range(40):
        a, open_a = new_round(a)
        b, open_b = old_round(b)
        for x, y in zip(jax.tree_util.tree_leaves((a, open_a)),
                        jax.tree_util.tree_leaves((b, open_b))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        received = int(jnp.sum(a.t_s))
    assert received > 64                 # the steal ran in earnest
