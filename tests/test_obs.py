"""ISSUE 7 acceptance tests: the search telemetry layer (repro.obs).

Covers (a) the metrics registry primitives (counters/gauges/histograms,
labels, the zero-cost disabled path), (b) the JSONL trace schema — writer
and reader both reject malformed records, (c) the centralized
ProgressEvent emission (unknown kinds raise at construction AND at
emit()), (d) the load-bearing invariant that telemetry is OBSERVATION
only: a traced+metered solve is bit-identical to a bare one, and (e) the
end-to-end pipeline: solve/service traces feed ``tools/trace_report.py``
whose per-instance node counts must sum to the engine total.
"""

import json
import pathlib
import sys

import numpy as np
import pytest

from repro import registry
from repro.obs import (MetricsRegistry, TraceError, TraceWriter, read_trace,
                       validate_record)
from repro.problems import gnp_graph
from repro.service import SolveRequest
from repro.solver import (EVENT_KINDS, ProgressEvent, Solver, SolverConfig,
                          emit)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "tools"))
import trace_report  # noqa: E402  (tools/ is not a package)

VC = registry.problem("vc", "gnp:14:30:5")


# -- metrics registry ---------------------------------------------------------


def test_counter_labels_and_values():
    r = MetricsRegistry()
    c = r.counter("reqs", "requests")
    c.inc()
    c.inc(2, scope="cross")
    c.inc(3, scope="cross")
    assert c.value() == 1
    assert c.value(scope="cross") == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_and_histogram():
    r = MetricsRegistry()
    g = r.gauge("depth", "queue depth")
    g.set(4)
    assert g.value() == 4
    h = r.histogram("ship", "depths", buckets=(1, 2, 4))
    for v in (1, 1, 3, 9):
        h.observe(v)
    got = h.value()
    assert got["count"] == 4 and got["sum"] == 14
    assert got["buckets"] == {"1": 2, "2": 0, "4": 1, "+Inf": 1}


def test_registry_idempotent_and_type_checked():
    r = MetricsRegistry()
    a = r.counter("x", "doc")
    assert r.counter("x", "doc") is a        # same instrument back
    with pytest.raises(ValueError, match="x"):
        r.gauge("x", "doc")                  # same name, different type


def test_disabled_registry_is_noop():
    r = MetricsRegistry(enabled=False)
    c = r.counter("x", "doc")
    c.inc(5)
    r.gauge("g", "doc").set(3)
    r.histogram("h", "doc").observe(1)
    snap = r.snapshot()
    assert snap.names() == ()
    assert snap.value("x") == 0              # missing counter reads as 0


def test_snapshot_is_a_frozen_copy():
    r = MetricsRegistry()
    c = r.counter("n", "doc")
    c.inc(2)
    snap = r.snapshot()
    c.inc(10)
    assert snap.value("n") == 2
    assert r.snapshot().value("n") == 12
    assert "n" in snap.to_dict()


# -- trace schema -------------------------------------------------------------


def test_trace_writer_validates_and_reader_roundtrips(tmp_path):
    path = str(tmp_path / "t.jsonl")
    w = TraceWriter(path)
    w.write("meta", schema=1, mode="solve", lanes=4, slots=1)
    w.write("round", round=1, open=3, active=2, nodes=8, steal_req=1,
            steal_recv=1, donated=1, inst_nodes=[8])
    w.write("summary", rounds=1, nodes=8, lane_nodes=[8, 0, 0, 0],
            inst_nodes=[8])
    w.close()
    records = read_trace(path)
    assert [r["t"] for r in records] == ["meta", "round", "summary"]


def test_trace_writer_rejects_unknown_kind_and_missing_fields(tmp_path):
    w = TraceWriter(str(tmp_path / "t.jsonl"))
    with pytest.raises(TraceError, match="unknown"):
        w.write("explosion", round=1)
    with pytest.raises(TraceError, match="missing"):
        w.write("round", round=1)            # lacks nodes/steal_*/...
    with pytest.raises(TraceError):
        validate_record({"round": 1})        # no "t" discriminator
    w.close()


def test_read_trace_reports_line_numbers(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"t":"meta","schema":1,"mode":"solve",'
                    '"lanes":4,"slots":1}\n'
                    '{"t":"nope"}\n')
    with pytest.raises(TraceError, match=":2:"):
        read_trace(str(path))


def test_trace_report_rejects_inconsistent_totals(tmp_path):
    records = [
        {"t": "meta", "schema": 1, "mode": "solve", "lanes": 2, "slots": 1},
        {"t": "summary", "rounds": 1, "nodes": 10, "lane_nodes": [4, 4],
         "inst_nodes": [10]},
    ]
    with pytest.raises(TraceError, match="per-lane"):
        trace_report.analyze(records)


# -- centralized event emission -----------------------------------------------


def test_progress_event_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown"):
        ProgressEvent(kind="explosion", round=1)
    assert "round" in EVENT_KINDS and "done" in EVENT_KINDS


def test_emit_validates_even_without_listener():
    emit(None, "round", round=1, open_work=0)          # silent but checked
    with pytest.raises(ValueError, match="unknown"):
        emit(None, "explosion", round=1)
    seen = []
    emit(seen.append, "done", round=3, open_work=0, best=7)
    assert len(seen) == 1 and seen[0].kind == "done" and seen[0].best == 7


def test_config_validates_trace_path():
    with pytest.raises(Exception):
        SolverConfig(trace_path="")


# -- telemetry is observation only --------------------------------------------


def test_solve_identical_with_telemetry_on_and_off(tmp_path):
    """The acceptance bar: tracing+metrics must not perturb the search.
    Same rounds, same stats (nodes, steals, incumbent), same payload."""
    base = dict(lanes=4, steps_per_round=16, bootstrap_rounds=2,
                bootstrap_steps=4)
    events_off, events_on = [], []
    off = Solver(SolverConfig(**base),
                 on_event=events_off.append).solve(VC)
    on = Solver(SolverConfig(**base, metrics=True,
                             trace_path=str(tmp_path / "t.jsonl")),
                on_event=events_on.append).solve(VC)
    assert off.stats == on.stats             # full SolveStats equality
    np.testing.assert_array_equal(off.payload, on.payload)
    rounds_off = [(e.round, e.open_work, e.best) for e in events_off
                  if e.kind == "round"]
    rounds_on = [(e.round, e.open_work, e.best) for e in events_on
                 if e.kind == "round"]
    assert rounds_off == rounds_on           # same incumbent trace per round


def test_round_events_carry_metrics_snapshot():
    events = []
    cfg = SolverConfig(lanes=4, steps_per_round=16, bootstrap_rounds=2,
                       bootstrap_steps=4, metrics=True)
    res = Solver(cfg, on_event=events.append).solve(VC)
    rounds = [e for e in events if e.kind == "round"]
    assert rounds and all(e.metrics is not None for e in rounds)
    final = [e for e in events if e.kind == "done"][0]
    assert final.metrics.value("engine_nodes") == res.stats.nodes
    # Without metrics=True the payload stays None (no snapshot cost).
    bare = []
    Solver(SolverConfig(lanes=4, steps_per_round=16, bootstrap_rounds=2,
                        bootstrap_steps=4), on_event=bare.append).solve(VC)
    assert all(e.metrics is None for e in bare)


# -- end-to-end: solve trace -> report ----------------------------------------


def test_solve_trace_report_cross_checks(tmp_path):
    trace = str(tmp_path / "solve.jsonl")
    solver = Solver(SolverConfig(lanes=4, steps_per_round=16,
                                 bootstrap_rounds=2, bootstrap_steps=4,
                                 metrics=True, trace_path=trace))
    res = solver.solve(VC)
    report = trace_report.analyze(read_trace(trace))
    assert report["mode"] == "solve" and report["lanes"] == 4
    assert report["nodes"] == res.stats.nodes
    assert sum(report["inst_nodes"]) == res.stats.nodes
    assert sum(report["lane_nodes"]) == res.stats.nodes
    # stats.t_s counts every task install, including host-side seeding;
    # the trace deliberately counts steals inside jitted rounds only
    # (the collector re-baselines after host-side lane surgery).
    assert report["steal_received"] <= res.stats.t_s
    assert report["steal_requests"] == res.stats.t_r
    assert 0.0 <= report["idle_pct"] <= 100.0
    assert 0.0 <= report["gini_lane_nodes"] <= 1.0
    assert report["best"] == [res.stats.best]
    snap = solver.metrics()
    assert snap.value("engine_nodes") == res.stats.nodes
    assert (snap.value("steal_received", scope="intra")
            + snap.value("steal_received", scope="cross")
            ) == report["steal_received"]
    # render() must produce the human table without raising
    assert "load balance" in trace_report.render(report)


def test_replay_steps_count_the_deepest_task_a_round(tmp_path):
    """``steal_replay_steps`` adds, round by round, the depth of the
    deepest task received: the batched replay's trip count."""
    trace = str(tmp_path / "solve.jsonl")
    solver = Solver(SolverConfig(lanes=8, steps_per_round=8,
                                 bootstrap_rounds=2, bootstrap_steps=4,
                                 metrics=True, trace_path=trace))
    solver.solve(VC)
    rounds = [r for r in read_trace(trace) if r["t"] == "round"]
    trips = [max(r["ship_depths"], default=0) for r in rounds]
    assert any(trips) and not all(trips)
    assert solver.metrics().value("steal_replay_steps") == sum(trips)


@pytest.mark.slow
def test_service_trace_report_k8_drain(tmp_path):
    """K=8 drain through the service with telemetry: the per-instance node
    counts in the report must sum to the engine total, request lifecycle
    counts must match the drain, and optima stay exact."""
    mix = [("vc", gnp_graph(12 + (i % 4), 0.3, seed=i)) for i in range(8)]
    trace = str(tmp_path / "svc.jsonl")
    svc = Solver(SolverConfig(lanes=16, steps_per_round=16, metrics=True,
                              trace_path=trace)).serve(
        max_n=max(g.n for _, g in mix), slots=4)
    for i, (fam, g) in enumerate(mix):
        svc.submit(SolveRequest(rid=i, graph=g, family=fam))
    results = svc.drain()
    for i, (fam, g) in enumerate(mix):
        want = Solver().oracle(registry.problem(fam, g)).best
        assert results[i].optimum == want, (i, g.name)
    snap = svc.metrics()
    report = trace_report.analyze(read_trace(trace))
    assert report["mode"] == "service" and report["slots"] == 4
    assert sum(report["inst_nodes"]) == report["nodes"]
    assert report["nodes"] == snap.value("engine_nodes")
    assert report["lifecycle"]["admit"] == 8
    assert report["lifecycle"]["retire"] == 8
    assert report["lifecycle"]["expire"] == 0
    assert report["max_queue_depth"] >= 1    # 8 requests over 4 slots
    wait = snap.value("service_wait_rounds")
    assert wait["count"] == 8                # every admit histogram-ed
    assert "requests" in trace_report.render(report)


def test_service_node_accounting_matches_budget_path():
    """With a collector active the driver reuses the collector's
    per-instance delta for node budgets — eviction must still fire."""
    svc = Solver(SolverConfig(lanes=8, steps_per_round=8,
                              metrics=True)).serve(max_n=18, slots=1)
    t = svc.submit(SolveRequest(rid=0, graph=gnp_graph(18, 0.3, seed=7),
                                family="vc", node_budget=5))
    res = t.result()
    assert res.status == "expired"
    assert t.nodes_used >= 5
