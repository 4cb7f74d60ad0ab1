"""Solver-service acceptance tests (ISSUE 2).

Determinism/isolation proof: a batched service run over K >= 4 mixed
instances (vc + ds, varied sizes) must return BITWISE-identical optima and
valid payloads vs. K independent SERIAL-RB oracles, for W in {8, 32}
lanes, including under a forced mid-run elastic restore onto a different
lane count.  Plus: the steal path must never pair lanes across instances
(tenant isolation), intra-device and cross-device.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import steal
from repro.core.engine import NO_INSTANCE, init_lanes
from repro.core.serial import serial_rb
from repro.problems import (gnp_graph, make_dominating_set_py,
                            make_vertex_cover_py, random_regularish_graph)
from _legacy import legacy_service
from _solutions import assert_feasible
from repro.service import SolveRequest, SolverService
from repro.service.batch_problem import StackedSpec, pack_instance

# K = 4 mixed instances: both families, varied sizes.
MIX = [
    ("vc", gnp_graph(18, 0.3, seed=7)),
    ("vc", random_regularish_graph(16, 4, seed=3)),
    ("ds", gnp_graph(12, 0.3, seed=9)),
    ("ds", gnp_graph(14, 0.25, seed=2)),
]


def oracle(family, graph):
    py = (make_vertex_cover_py(graph) if family == "vc"
          else make_dominating_set_py(graph))
    return serial_rb(py)[0]


ORACLES = [oracle(f, g) for f, g in MIX]


def run_requests(svc):
    reqs = [SolveRequest(rid=i, graph=g, family=f)
            for i, (f, g) in enumerate(MIX)]
    for r in reqs:
        svc.submit(r)
    return reqs, svc.drain()


@pytest.mark.parametrize("lanes", [8, 32])
def test_service_matches_serial_oracles(lanes):
    svc = legacy_service(max_n=18, slots=4, num_lanes=lanes,
                        steps_per_round=16)
    _, results = run_requests(svc)
    for i, (family, graph) in enumerate(MIX):
        assert results[i].optimum == ORACLES[i], (i, family, graph.name)
        assert_feasible(family, graph, results[i].payload,
                        results[i].optimum)


# -- kernel backend: pallas stacked evaluate == jnp stacked evaluate ----------
# (the stacked-service leg of the DESIGN.md §5.4 backend-equivalence sweep)


def _mixed_tables(n=14):
    spec = StackedSpec(n=n, k=3)
    tables_np = spec.empty_tables()
    mix = [("vc", gnp_graph(14, 0.3, seed=7)), ("ds", gnp_graph(12, 0.3, seed=9)),
           ("vc", gnp_graph(10, 0.4, seed=1))]
    for slot, (fam, g) in enumerate(mix):
        adj, fm, f = pack_instance(g, 0 if fam == "vc" else 1, n)
        tables_np.adj[slot], tables_np.fullm[slot] = adj, fm
        tables_np.family[slot] = f
    return spec, type(tables_np)(*(jnp.asarray(t) for t in tables_np))


def test_stacked_backend_nodeeval_bitwise_identical():
    """Walk both family trees from every slot root: each NodeEval field must
    agree between the jnp and the batched-Pallas stacked evaluate."""
    from repro.core.api import INF_VALUE
    spec, tables = _mixed_tables()
    bj = spec.bind(tables)
    bp = spec.bind(tables, backend="pallas", tile=16)
    frontier = [bj.instance_root(jnp.int32(s)) for s in range(spec.k)]
    seen = 0
    while frontier and seen < 60:
        state = frontier.pop()
        ej = bj.evaluate(state, INF_VALUE)
        ep = bp.evaluate(state, INF_VALUE)
        for a, b in zip(jax.tree_util.tree_leaves(ej),
                        jax.tree_util.tree_leaves(ep)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        seen += 1
        if not bool(ej.is_solution):
            frontier += [ej.left, ej.right]
    assert seen == 60


def test_stacked_bind_rejects_unknown_backend():
    spec, tables = _mixed_tables()
    with pytest.raises(ValueError):
        spec.bind(tables, backend="cuda")


def test_service_pallas_backend_matches_serial_oracles():
    """Full continuous-batching drain through the batched stacked kernel:
    every tenant still lands exactly on its serial optimum."""
    svc = legacy_service(max_n=18, slots=4, num_lanes=8, steps_per_round=16,
                        backend="pallas")
    _, results = run_requests(svc)
    for i, (family, graph) in enumerate(MIX):
        assert results[i].optimum == ORACLES[i], (i, family, graph.name)
        assert_feasible(family, graph, results[i].payload,
                        results[i].optimum)


def test_service_backend_crosses_checkpoints(tmp_path):
    """Save under jnp, restore under pallas (backend is an execution choice,
    not checkpoint state — driver docstring): identical results."""
    svc = legacy_service(max_n=18, slots=4, num_lanes=8, steps_per_round=4)
    for i, (f, g) in enumerate(MIX):
        svc.submit(SolveRequest(rid=i, graph=g, family=f))
    svc.step_round()
    svc.step_round()
    assert any(r >= 0 for r in svc.slot_rid)
    path = str(tmp_path / "svc.ckpt")
    svc.save(path)

    svc2 = SolverService.restore(path, num_lanes=8, steps_per_round=16,
                                 backend="pallas")
    results = svc2.drain()
    for i, (family, graph) in enumerate(MIX):
        assert results[i].optimum == ORACLES[i], (i, family, graph.name)


@pytest.mark.parametrize("w_before,w_after", [(8, 32), (32, 7)])
def test_service_elastic_restore_midrun(w_before, w_after, tmp_path):
    """Forced mid-run elastic restore: save with K instances in flight on
    W lanes, restore onto W' != W, drain — every instance still reaches
    its serial optimum and the pending pool empties."""
    svc = legacy_service(max_n=18, slots=4, num_lanes=w_before,
                        steps_per_round=4)
    for i, (f, g) in enumerate(MIX):
        svc.submit(SolveRequest(rid=i, graph=g, family=f))
    svc.step_round()
    svc.step_round()
    assert any(r >= 0 for r in svc.slot_rid)     # genuinely mid-flight
    path = str(tmp_path / "svc.ckpt")
    svc.save(path)

    svc2 = SolverService.restore(path, num_lanes=w_after,
                                 steps_per_round=16)
    results = svc2.drain()
    for i, (family, graph) in enumerate(MIX):
        assert results[i].optimum == ORACLES[i], (i, family, graph.name)
        assert_feasible(family, graph, results[i].payload,
                        results[i].optimum)
    assert not svc2.pool                          # pending pool drained


def test_service_continuous_batching_reuses_slots():
    """More requests than slots: retirement must free slots for the queue
    and every backlogged request must still be exact."""
    reqs = [SolveRequest(rid=100 + i, graph=g, family=f)
            for i, (f, g) in enumerate(MIX * 2)]
    svc = legacy_service(max_n=18, slots=2, num_lanes=8, steps_per_round=16)
    for r in reqs:
        svc.submit(r)
    results = svc.drain()
    for i, (family, graph) in enumerate(MIX * 2):
        assert results[100 + i].optimum == ORACLES[i % len(MIX)]


# -- admission: typed errors at submit() time (ISSUE 4 satellite) -------------


def test_submit_rejects_unregistered_family():
    from repro.service import AdmissionError
    svc = legacy_service(max_n=18, slots=2, num_lanes=4)
    with pytest.raises(AdmissionError, match="unknown problem family"):
        svc.submit(SolveRequest(rid=0, graph=MIX[0][1], family="tsp"))
    assert not svc.queue                      # nothing silently enqueued


def test_submit_rejects_unservable_family():
    """subset sum is registered (CLI + oracle) but has no service packing:
    the failure is a typed AdmissionError at submit(), not a crash deep
    inside table packing."""
    from repro.service import AdmissionError
    svc = legacy_service(max_n=18, slots=2, num_lanes=4)
    with pytest.raises(AdmissionError, match="not servable"):
        svc.submit(SolveRequest(rid=0, graph=MIX[0][1], family="ss"))


def test_submit_rejects_oversized_instance():
    from repro.service import AdmissionError
    svc = legacy_service(max_n=14, slots=2, num_lanes=4)
    with pytest.raises(AdmissionError, match="max_n"):
        svc.submit(SolveRequest(rid=0, graph=gnp_graph(20, 0.3, seed=1),
                                family="vc"))


# -- tenant isolation: stealing never crosses instances -----------------------


def _stacked_lanes(num_lanes):
    """A 2-instance stacked problem + idle lane pool for steal surgery."""
    spec = StackedSpec(n=12, k=2)
    tables_np = spec.empty_tables()
    for slot, (f, g) in enumerate([("vc", gnp_graph(12, 0.4, seed=1)),
                                   ("vc", gnp_graph(10, 0.4, seed=2))]):
        adj, fm, fam = pack_instance(g, 0, 12)
        tables_np.adj[slot], tables_np.fullm[slot] = adj, fm
        tables_np.family[slot] = fam
    tables = type(tables_np)(*(jnp.asarray(t) for t in tables_np))
    prob = spec.bind(tables)
    lanes = init_lanes(prob, num_lanes, seed_root=False)
    return prob, lanes


def _with_donor(lanes, lane, inst, depth=4):
    """Make ``lane`` an active donor of ``inst`` with open LEFT slots."""
    idx = np.asarray(lanes.idx).copy()
    idx[lane, :depth] = 0                          # LEFT: open right siblings
    return lanes._replace(
        idx=jnp.asarray(idx),
        depth=lanes.depth.at[lane].set(depth),
        active=lanes.active.at[lane].set(True),
        inst=lanes.inst.at[lane].set(inst))


def test_intra_device_steal_is_instance_scoped():
    prob, lanes = _stacked_lanes(8)
    # Donors only in instance 0; idle lanes unbound except two thieves
    # bound to instance 1.
    lanes = lanes._replace(
        inst=jnp.full_like(lanes.inst, NO_INSTANCE).at[3].set(1).at[5].set(1))
    lanes = _with_donor(lanes, 0, inst=0)
    out = steal.balance_device(prob, lanes)
    # Nothing may move: the global matching would have paired lane 0 -> 3.
    assert int(out.donated.sum()) == 0
    assert not bool(out.active[3]) and not bool(out.active[5])
    np.testing.assert_array_equal(np.asarray(out.inst),
                                  np.asarray(lanes.inst))

    # Now give instance 1 its own donor: only same-instance pairs may form.
    lanes2 = _with_donor(lanes, 1, inst=1, depth=3)
    out2 = steal.balance_device(prob, lanes2)
    assert bool(out2.active[3])                   # thief of inst 1 fed
    assert int(out2.inst[3]) == 1
    donated = np.asarray(out2.donated) - np.asarray(lanes2.donated)
    assert donated[1] == 1 and donated[0] == 0    # inst-0 donor untouched


def test_unbound_lanes_never_steal():
    prob, lanes = _stacked_lanes(4)
    lanes = _with_donor(lanes, 0, inst=0)
    # Remaining idle lanes are unbound (NO_INSTANCE): must stay idle.
    assert int(lanes.inst[1]) == 0
    lanes = lanes._replace(
        inst=lanes.inst.at[1].set(NO_INSTANCE).at[2].set(NO_INSTANCE)
        .at[3].set(NO_INSTANCE))
    out = steal.balance_device(prob, lanes)
    assert int(out.donated.sum()) == 0
    assert int(out.active.sum()) == 1


_CROSS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist
from repro.core.engine import Lanes, init_lanes
from repro.problems import gnp_graph
from repro.service.batch_problem import StackedSpec, pack_instance

D, W = 8, 2
spec = StackedSpec(n=12, k=2)
tables_np = spec.empty_tables()
for slot, g in enumerate([gnp_graph(12, 0.4, seed=1),
                          gnp_graph(10, 0.4, seed=2)]):
    adj, fm, fam = pack_instance(g, 0, 12)
    tables_np.adj[slot], tables_np.fullm[slot] = adj, fm
    tables_np.family[slot] = fam
tables = type(tables_np)(*(jnp.asarray(t) for t in tables_np))
prob = spec.bind(tables)
mesh = jax.make_mesh((D,), ("workers",))


def steal_fn(max_ship):
    def f(lanes):
        return dist.cross_device_steal(prob, lanes, ("workers",), max_ship)

    proto = init_lanes(prob, 1, seed_root=False)
    specs = Lanes(**{f_: jax.tree_util.tree_map(
        lambda leaf: P() if f_ in ("best", "steps", "best_payload")
        else P(("workers",)), getattr(proto, f_))
        for f_ in Lanes._fields})
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(specs,),
                                 out_specs=specs, check_vma=False))


STEAL = steal_fn(16)

# Device 0 holds donors of instance 0; devices 2-3 hold thieves of
# instance 1 and device 5 a thief of instance 0.  Only the inst-0 thief
# may be fed, by an inst-0 donor.
lanes = init_lanes(prob, D * W, seed_root=False)
idx = np.asarray(lanes.idx).copy()
inst = np.full(D * W, -1, np.int32)
active = np.zeros(D * W, bool)
depth = np.zeros(D * W, np.int32)
for lane in (0, 1):                        # donors, inst 0, open LEFTs
    idx[lane, :4] = 0
    depth[lane] = 4
    active[lane] = True
    inst[lane] = 0
for lane in (4, 6):                        # thieves bound to inst 1
    inst[lane] = 1
inst[10] = 0                               # thief bound to inst 0
lanes = lanes._replace(idx=jnp.asarray(idx), inst=jnp.asarray(inst),
                       active=jnp.asarray(active),
                       depth=jnp.asarray(depth))
from repro.core.checkpoint import rebuild_stacks
lanes = dist._shard_lanes(rebuild_stacks(prob, lanes), mesh)
out = jax.tree_util.tree_map(np.asarray, STEAL(lanes))

newly = np.flatnonzero(out.active & ~np.asarray(lanes.active))
res = {
    "donated": int(out.donated.sum()),
    "newly_active": [int(x) for x in newly],
    "inst_of_new": [int(out.inst[x]) for x in newly],
}

# Budget-starvation regression: with max_ship=1, device 0 holds one
# donor of instance 0 (which has ZERO demand anywhere) and one donor of
# instance 1 (demanded on device 2).  A donatable-count budget would hand
# the whole advertisement to instance 0 and ship nothing; the
# demand-limited quota must ship the instance-1 task.
STEAL1 = steal_fn(1)
lanes = init_lanes(prob, D * W, seed_root=False)
idx = np.asarray(lanes.idx).copy()
inst = np.full(D * W, -1, np.int32)
active = np.zeros(D * W, bool)
depth = np.zeros(D * W, np.int32)
for lane, i in ((0, 0), (1, 1)):           # device 0: donors of inst 0 & 1
    idx[lane, :4] = 0
    depth[lane] = 4
    active[lane] = True
    inst[lane] = i
inst[4] = inst[5] = 1                      # device 2: thieves of inst 1
lanes = lanes._replace(idx=jnp.asarray(idx), inst=jnp.asarray(inst),
                       active=jnp.asarray(active),
                       depth=jnp.asarray(depth))
lanes = dist._shard_lanes(rebuild_stacks(prob, lanes), mesh)
out = jax.tree_util.tree_map(np.asarray, STEAL1(lanes))
newly = np.flatnonzero(out.active & ~np.asarray(lanes.active))
res["starve_donated"] = int(out.donated.sum())
res["starve_new_inst"] = [int(out.inst[x]) for x in newly]
print("RESULT " + json.dumps(res))
"""


def test_cross_device_steal_is_instance_scoped():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _CROSS_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    import json
    res = json.loads(line[len("RESULT "):])
    # Exactly one task may move: donor(inst 0) -> the single inst-0 thief.
    assert res["donated"] == 1, res
    assert res["newly_active"] == [10], res
    assert res["inst_of_new"] == [0], res
    # Budget starvation: a zero-demand instance must not crowd a demanded
    # one out of the max_ship advertisement.
    assert res["starve_donated"] == 1, res
    assert res["starve_new_inst"] == [1], res
