"""The readers of a four-chip cell on a synthetic four-device trace: the
mesh's own (cross-chip steal, the incumbent's election, the collectives'
share) and the solve readers that ``vc_c250.solve4`` shares with the
one-chip cell, each against the value worked out by hand."""

import numpy as np
import pytest

import harness
import program_trace as pt
import trace_reduce as tr

CHIPS = 4
ROUNDS = (200, 600)
MESH_READERS = ("cross_steal_us_per_round.solve",
                "share_best_us_per_round.solve", "collective_share.solve")
SHARED_READERS = ("idle_share.solve", "lane_occupancy.solve",
                  "device_ns_per_lane_step.solve", "steal_us_per_round.solve",
                  "host_us_per_round.solve", "entry_ms.solve")


def round_ops(t, d, mesh=True):
    """One round's device ops on chip ``d`` from ``t``: the expand loop over
    an evaluate, the chip's own steal (20 + 2d ns), and on a mesh the
    cross-chip steal (a fusion and an all-gather, 20 + 2d), the election
    (two all-reduces, 10 + d, and a fusion reading the first) and the
    open-work all-reduce (5)."""
    ops = [("%while.1 = (u32[4]) while()", t, t + 100, ""),
           ("%fusion.1 = u32[4] fusion()", t + 10, t + 40, "engine.evaluate"),
           ("%fusion.2 = u32[4] fusion()", t + 100, t + 120 + 2 * d,
            "steal.balance_device")]
    return ops if not mesh else ops + [
           ("%fusion.3 = s32[16,8] fusion()", t + 130, t + 140,
            "steal.cross_device"),
           ("%all-gather.1 = s32[64,8] all-gather(%fusion.3)", t + 140,
            t + 150 + 2 * d, "steal.cross_device"),
           ("%pmin.14 = s32[1]{0} all-reduce(%fusion.3), channel_id=1, "
            "replica_groups={{0,1,2,3}}, to_apply=%region_4.5", t + 160,
            t + 165 + d, "round.share_best"),
           ("%psum.14 = u32[1,5]{1,0:T(1,128)} all-reduce-start(%fusion.5)",
            t + 170, t + 175, "round.share_best"),
           ("%fusion.4 = (s32[1], u32[2]) fusion(%pmin.14, %psum.14), "
            "kind=kLoop, calls=%all-reduce.9", t + 175, t + 178,
            "round.share_best"),
           ("all-reduce.3", t + 180, t + 185, "round.open_work")]


def four_chip_trace(scoped=True, chips=CHIPS):
    """A window [0, 1000) with one traced solve [10, 990) of two rounds, at
    200 and 600, on ``chips`` chips whose ops last longer with the chip's
    id (a mesh round on more than one)."""
    ops, scoped_ops, modules = {}, {}, {}
    for d in range(chips):
        events = [e for t in ROUNDS for e in round_ops(t, d, chips > 1)]
        names = [e[0] for e in events]
        start = np.array([e[1] for e in events], np.int64)
        end = np.array([e[2] for e in events], np.int64)
        ops[d] = tr.Ops(names, start, end)
        scoped_ops[d] = pt.ScopedOps(names, start, end,
                                     [e[3] if scoped else "" for e in events])
        modules[d] = tr.Ops(["jit_round_fn(1)"] * 2,
                            np.array([t + d for t in ROUNDS], np.int64),
                            np.array([t + 190 for t in ROUNDS], np.int64))
    spans = {name: [] for name in tr.SPANS}
    spans.update({"harness.window": [(0, 1000)], "solve": [(10, 990)]})
    summary = tr.Summary(tr.RawTrace(ops, spans, modules))
    program = {"repro.solve.prepare": [(10, 100)],
               "repro.solve.round": [(150, 400), (550, 800)],
               "repro.solve.dispatch": [(150, 200), (550, 560)]}
    raw = pt.ProgramTrace(program, scoped_ops)
    return summary, pt.Program(summary, raw)


def view(summary, program):
    host = {"solves": [dict(rounds=2, lanes=CHIPS * 4, nodes=40,
                            traced=True)],
            "steps": 2, "lanes_per_chip": 4}
    v = harness.View(host, summary)
    v.program = program
    return v


def per_chip_mean(f):
    return float(np.mean([f(d) for d in range(CHIPS)]))


def test_the_mesh_readers_take_the_mean_over_chips():
    v = view(*four_chip_trace())
    read = {name: harness.reader(name).read(v) for name in MESH_READERS}
    # fusion.4 names collectives in its text, and is no collective; an op
    # without HLO text is one by its name.
    busy = 2 * per_chip_mean(lambda d: (120 + 2 * d) + (20 + 2 * d)
                             + (5 + d) + 5 + 3 + 5)
    collective = 2 * per_chip_mean(lambda d: (10 + 2 * d) + (5 + d) + 5 + 5)
    assert v.trace.busy_ns(10, 990) == pytest.approx(busy)
    assert read["cross_steal_us_per_round.solve"] == pytest.approx(
        per_chip_mean(lambda d: 20 + 2 * d) / 1e3)
    assert read["share_best_us_per_round.solve"] == pytest.approx(
        per_chip_mean(lambda d: 13 + d) / 1e3)
    assert read["collective_share.solve"] == pytest.approx(
        100 * collective / busy)


def test_the_shared_readers_are_right_on_four_chips():
    v = view(*four_chip_trace())
    read = {name: harness.reader(name).read(v) for name in SHARED_READERS}
    busy_round = per_chip_mean(lambda d: 158 + 5 * d)
    assert read["idle_share.solve"] == pytest.approx(
        100 * (1 - 2 * busy_round / 1000))
    # Nodes over the lane-steps of all chips' lanes.
    assert read["lane_occupancy.solve"] == pytest.approx(
        100 * 40 / (2 * 2 * CHIPS * 4))
    # Busy time of one chip (the mean) over one chip's lane-steps.
    assert read["device_ns_per_lane_step.solve"] == pytest.approx(
        2 * busy_round / (2 * 2 * 4))
    assert read["steal_us_per_round.solve"] == pytest.approx(
        per_chip_mean(lambda d: 20 + 2 * d) / 1e3)
    # The second round's span [550, 800) less the mean busy time in it.
    assert read["host_us_per_round.solve"] == pytest.approx(
        (250 - busy_round) / 1e3)
    # The first run of the round on any chip: chip 0's, at 200.
    assert read["entry_ms.solve"] == pytest.approx((200 - 10) / 1e6)


def test_without_scopes_only_the_collective_share_reads():
    v = view(*four_chip_trace(scoped=False))
    assert harness.reader("cross_steal_us_per_round.solve").read(v) is None
    assert harness.reader("share_best_us_per_round.solve").read(v) is None
    assert harness.reader("collective_share.solve").read(v) > 0


def test_a_one_chip_round_gives_the_mesh_readers_nothing():
    v = view(*four_chip_trace(chips=1))
    for name in MESH_READERS:
        assert harness.reader(name).read(v) is None, name


def test_the_mesh_readers_read_nothing_without_a_trace():
    for name in MESH_READERS:
        assert harness.reader(name).read(harness.View({}, None)) is None
