"""The incumbent across a mesh: value and solution elected together.

After every round of a mesh solve or of the sharded service, every device
must hold the same ``(best, best_payload)``, and the payload must be a
solution of value ``best``.  ``share_best`` takes the ``pmin`` of the value
and then the solution of the lowest-ranked device holding it.

The mesh cases run in one subprocess with 4 forced host devices (the rest
of the suite sees one device); the one-chip round is checked in process.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from repro import registry
from repro.core.distributed import make_round
from repro.core.engine import init_lanes

ROOT = os.path.join(os.path.dirname(__file__), "..")
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter")

# (devices, n, p, seed, lanes a device): G(n, p) draws solved whole on a
# 1-D mesh, 8 steps a round.  Before the solution was elected with its
# value, every case held differing incumbents on some round, and the draws
# marked below returned a payload that is not a cover of the optimum's size.
SOLVES = [
    (2, 24, 0.3, 3, 4),
    (2, 30, 0.25, 2, 8),       # failed so
    (2, 36, 0.2, 1, 4),        # failed so
    (4, 24, 0.3, 1, 8),
    (4, 30, 0.25, 1, 4),       # failed so
    (4, 36, 0.2, 1, 8),        # failed so
    (4, 36, 0.2, 2, 4),        # failed so
]
SERVICE = [(0, 22, 0.3, 1), (1, 26, 0.25, 2), (2, 30, 0.25, 1),
           (3, 28, 0.2, 4), (4, 24, 0.3, 5)]     # (rid, n, p, seed)

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(ROOT, "bench"))
import plain_ref
from repro.core import distributed as dist
from repro.core.engine import INF_VALUE, init_lanes
from repro.core.serial import serial_rb
from repro.problems import gnp_graph, make_vertex_cover, make_vertex_cover_py
from repro.service import SolveRequest
from repro.solver import Solver, SolverConfig

assert len(jax.devices()) == 4, jax.devices()
out = {"elect": {}, "solve": {}, "service": {}}


def dense_of(g):
    v = np.arange(g.n)
    return ((g.adj[:, v // 32] >> (v % 32).astype(np.uint32)) & 1) == 1


def replicas(arr):
    return [np.asarray(s.data) for s in arr.addressable_shards]


# -- share_best alone: device d holds its own value and payload ------------
K, WORDS = 2, 3
prob = make_vertex_cover(gnp_graph(8, 0.5, seed=1))
proto = init_lanes(prob, 1)
for d, values in ((2, [[9, 5], [7, 5]]),
                  (4, [[9, 5], [7, 6], [7, 4], [8, 4]])):
    mesh = jax.make_mesh((d,), ("workers",), devices=jax.devices()[:d])
    values = np.asarray(values, np.int32)                   # [D, K]
    payloads = (np.arange(d * K * WORDS, dtype=np.uint32).reshape(
        d, K, WORDS) + 1) * 7                               # all distinct

    def elect(b, p):
        lanes = proto._replace(best=b[0], best_payload=p[0])
        lanes = dist.share_best(lanes, ("workers",))
        return lanes.best[None], lanes.best_payload[None]

    fn = jax.jit(jax.shard_map(elect, mesh=mesh,
                               in_specs=(P("workers"), P("workers")),
                               out_specs=(P("workers"), P("workers")),
                               check_vma=False))
    b, p = fn(jnp.asarray(values), jnp.asarray(payloads))
    out["elect"][str(d)] = {"values": values.tolist(),
                            "payloads": payloads.tolist(),
                            "best": np.asarray(b).tolist(),
                            "payload": np.asarray(p).tolist()}

# -- whole mesh solves: every round, every device holds one incumbent ------
for d, n, p, seed, lanes in SOLVES:
    mesh = jax.make_mesh((d,), ("workers",), devices=jax.devices()[:d])
    g = gnp_graph(n, p, seed)
    dense = dense_of(g)
    split = []

    def watch(ev):
        if ev.kind != "round":
            return
        bs, ps = replicas(ev.lanes.best), replicas(ev.lanes.best_payload)
        same = all(np.array_equal(b, bs[0]) for b in bs) and all(
            np.array_equal(q, ps[0]) for q in ps)
        best = int(bs[0][0])
        if not same or (best < INF_VALUE and plain_ref.solution_gap(
                "vc", dense, ps[0][0], best)):
            split.append(ev.round)

    res = Solver(SolverConfig(lanes=lanes, steps_per_round=8, mesh=mesh),
                 on_event=watch).solve(make_vertex_cover(g))
    out["solve"][f"{d}-{n}-{p}-{seed}-{lanes}"] = {
        "best": res.stats.best, "rounds": res.stats.rounds,
        "split_rounds": split,
        "gap": bool(plain_ref.solution_gap("vc", dense, res.payload,
                                           res.stats.best)),
        "serial": serial_rb(make_vertex_cover_py(g))[0],
        "plain_ref": plain_ref.min_vertex_cover(dense)}

# -- the sharded service on 4 devices ---------------------------------------
mesh = jax.make_mesh((4,), ("workers",))
graphs = {rid: gnp_graph(n, p, seed) for rid, n, p, seed in SERVICE}
svc = Solver(SolverConfig(lanes=4, steps_per_round=8, mesh=mesh)).serve(
    max_n=max(g.n for g in graphs.values()), slots=2)
tickets = {rid: svc.submit(SolveRequest(rid=rid, graph=g, family="vc"))
           for rid, g in graphs.items()}
svc.drain()
for rid, g in graphs.items():
    r, dense = svc.results[rid], dense_of(g)
    out["service"][str(rid)] = {
        "status": tickets[rid].status.value, "optimum": r.optimum,
        "gap": bool(plain_ref.solution_gap("vc", dense, r.payload,
                                           r.optimum)),
        "plain_ref": plain_ref.min_vertex_cover(dense)}

# -- the mesh round does hold the collectives the one-chip round lacks ----
text = dist.make_distributed_round(prob, mesh, 8).lower(
    dist._shard_lanes(init_lanes(prob, 16), mesh)).compile().as_text()
out["mesh_round_collectives"] = [c for c in COLLECTIVES if c in text]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    script = (f"ROOT = {os.path.abspath(ROOT)!r}\n"
              f"SOLVES = {SOLVES!r}\nSERVICE = {SERVICE!r}\n"
              f"COLLECTIVES = {COLLECTIVES!r}\n" + _SCRIPT)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("devices,owners", [(2, [1, 0]), (4, [1, 2])])
def test_share_best_elects_the_holders_solution_lowest_device_on_ties(
        mesh_result, devices, owners):
    r = mesh_result["elect"][str(devices)]
    values, payloads = r["values"], r["payloads"]
    for k, owner in enumerate(owners):
        best = min(v[k] for v in values)
        assert values[owner][k] == best
        assert all(v[k] > best for v in values[:owner])
        for d in range(devices):
            assert r["best"][d][k] == best
            assert r["payload"][d][k] == payloads[owner][k], (d, k)


@pytest.mark.parametrize("case", SOLVES,
                         ids=lambda c: "d{}-n{}-p{}-s{}-l{}".format(*c))
def test_mesh_solve_returns_a_minimum_cover_held_by_every_device(
        mesh_result, case):
    r = mesh_result["solve"]["-".join(str(x) for x in case)]
    assert r["split_rounds"] == [], r
    assert not r["gap"], r
    assert r["best"] == r["serial"] == r["plain_ref"], r


def test_sharded_service_returns_minimum_covers(mesh_result):
    assert set(mesh_result["service"]) == {str(c[0]) for c in SERVICE}
    for rid, r in mesh_result["service"].items():
        assert r["status"] == "done", (rid, r)
        assert not r["gap"], (rid, r)
        assert r["optimum"] == r["plain_ref"], (rid, r)


def test_mesh_round_holds_collectives(mesh_result):
    assert "all-reduce" in mesh_result["mesh_round_collectives"]
    assert "all-gather" in mesh_result["mesh_round_collectives"]


def test_one_chip_round_lowers_to_no_collective():
    prob = registry.problem("vc", "gnp:20:30:1").build()
    text = jax.jit(make_round(prob, 8)).lower(
        init_lanes(prob, 8)).compile().as_text()
    assert [c for c in COLLECTIVES if c in text] == []
