"""The reduction from a trace to numbers, on small synthetic traces."""

import numpy as np
import pytest

import trace_reduce as tr


def ops(*events):
    names = [e[0] for e in events]
    start = np.array([e[1] for e in events], np.int64)
    end = np.array([e[2] for e in events], np.int64)
    return tr.Ops(names, start, end)


def raw(device_ops, **spans):
    full = {name: [] for name in tr.SPANS}
    full.update({k.replace("__", "."): v for k, v in spans.items()})
    return tr.RawTrace(device_ops, full)


def test_union_merges_overlaps_and_nesting():
    s, e = tr.union(np.array([0, 5, 2, 20, 21], np.int64),
                    np.array([3, 10, 4, 30, 25], np.int64))
    assert s.tolist() == [0, 5, 20] and e.tolist() == [4, 10, 30]
    assert tr.covered(s, e, 2, 22) == 2 + 5 + 2


def test_idle_share_is_one_minus_busy_union_over_the_window():
    # Window [0, 100); device busy [10, 30) and [20, 50) -> union 40.
    summary = tr.Summary(raw({0: ops(("fusion.1", 10, 30),
                                     ("fusion.2", 20, 50),
                                     ("fusion.3", 150, 160))},
                             harness__window=[(0, 100)]))
    assert summary.window_ns == 100
    assert summary.busy_ns() == 40
    assert summary.idle_share() == pytest.approx(0.6)


def test_busy_is_the_mean_over_devices():
    summary = tr.Summary(raw({0: ops(("a", 0, 50)), 1: ops(("a", 0, 10))},
                             harness__window=[(0, 100)]))
    assert summary.busy_ns() == 30
    assert summary.idle_share() == pytest.approx(0.7)


def test_collective_share_counts_only_collective_ops():
    summary = tr.Summary(raw({0: ops(("fusion.7", 0, 60),
                                     ("all-gather-start.2", 60, 70),
                                     ("all-reduce.3", 65, 80),
                                     ("collective-permute-done", 90, 95))},
                             harness__window=[(0, 100)]))
    assert summary.busy_ns() == 85
    assert summary.collective_ns() == 25


def test_time_inside_an_annotation_and_the_first_program_after_it():
    trace = raw({0: ops(("a", 30, 40), ("b", 70, 90))},
                harness__window=[(0, 100)],
                solve=[(20, 50), (60, 95), (200, 300)])
    trace = trace._replace(modules={0: ops(("jit_init(1)", 25, 28),
                                           ("jit_round_fn(7)", 30, 40),
                                           ("jit_round_fn(7)", 70, 90))})
    summary = tr.Summary(trace)
    assert summary.spans("solve") == [(20, 50), (60, 95)]
    assert summary.busy_ns(20, 50) == 10 and summary.busy_ns(60, 95) == 20
    assert summary.first_module_after(20, "round_fn") == 30
    assert summary.first_module_after(20, "init") == 25
    assert summary.first_module_after(60, "round_fn") == 70
    assert summary.first_module_after(95, "round_fn") is None


def test_op_names_are_cut_to_the_hlo_name():
    assert tr.short_name("%fusion.12 = u32[4]{0} fusion(%x), kind=kLoop") \
        == "fusion.12"
    assert tr.short_name("all-gather.3") == "all-gather.3"


def test_breakdown_names_ops_and_gaps_by_the_host_annotation():
    summary = tr.Summary(raw(
        {0: ops(("%fusion.1 = u32[2] fusion()", 10, 20), ("fusion.1", 30, 35),
                ("while.2", 60, 100))},
        harness__window=[(0, 100)],
        service__step_round=[(5, 40), (55, 100)],
        service__submit=[(40, 55)]))
    assert summary.top_ops(2) == [["while.2", 40e-9], ["fusion.1", 15e-9]]
    gaps = summary.idle_gaps(10)
    assert gaps[0] == ["service.step_round", 25e-9]      # [35, 60)
    assert sorted(g[1] for g in gaps) == pytest.approx([10e-9, 10e-9, 25e-9])
    assert summary.host_label(0) == "harness"
    assert summary.host_label(45) == "service.submit"


def test_a_trace_without_a_window_or_device_ops_is_refused():
    with pytest.raises(ValueError):
        tr.Summary(raw({0: ops(("a", 0, 1))}))
    with pytest.raises(ValueError):
        tr.Summary(raw({}, harness__window=[(0, 10)]))
