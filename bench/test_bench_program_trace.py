"""The program's spans and scopes in a trace (``program_trace``) and the
readers built on them, on small synthetic traces."""

import glob

import numpy as np
import pytest

import harness
import program_trace as pt
import trace_reduce as tr

NEW_READERS = ("prepare_ms.solve", "first_dispatch_ms.solve",
               "round_traces.solve", "host_us_per_round.solve",
               "steal_us_per_round.solve", "evaluate_ns_per_lane_step.solve",
               "select_advance_ns_per_lane_step.solve",
               "unscoped_share.solve")
OLD_READERS = ("idle_share.solve", "entry_ms.solve",
               "device_ns_per_lane_step.solve", "lane_occupancy.solve")


def round_ops(t):
    """One round's device ops from ``t``: the expand loop (100 ns, no
    scope) over an advance (30), an evaluate (20) and a select (10), then
    the steal's loop (20) over a copy the profiler gives the loop's scope
    (10), and the open-work count (10)."""
    return [("while.1", t, t + 100, ""),
            ("fusion.5", t + 10, t + 40, "engine.advance"),
            ("fusion.1", t + 50, t + 70, "engine.evaluate"),
            ("fusion.2", t + 70, t + 80, "engine.select"),
            ("while.2", t + 100, t + 120, "steal.balance_device"),
            ("copy.2", t + 105, t + 115, "steal.balance_device"),
            ("fusion.4", t + 120, t + 130, "round.open_work")]


def trace(scoped=True, program_spans=True):
    """A window [0, 1000) with one traced solve [10, 990) of three rounds
    of ``round_ops`` at 250, 450 and 750."""
    events = round_ops(250) + round_ops(450) + round_ops(750)
    names = [e[0] for e in events]
    start = np.array([e[1] for e in events], np.int64)
    end = np.array([e[2] for e in events], np.int64)
    scopes = [e[3] if scoped else "" for e in events]
    spans = {name: [] for name in tr.SPANS}
    spans.update({"harness.window": [(0, 1000)], "solve": [(10, 990)]})
    modules = tr.Ops(["jit_round_fn(1)"] * 3,
                     np.array([250, 450, 750], np.int64),
                     np.array([380, 580, 880], np.int64))
    summary = tr.Summary(tr.RawTrace({0: tr.Ops(names, start, end)}, spans,
                                     {0: modules}))
    program = {}
    if program_spans:
        program = {
            "repro.solve.prepare": [(10, 100)],
            "repro.solve.round": [(100, 400), (400, 700), (700, 980)],
            "repro.solve.dispatch": [(100, 250), (400, 420), (700, 720)],
            "repro.solve.readback": [(380, 400), (680, 700), (960, 980)],
            "repro.round.trace": [(110, 200)],
            "repro.solve.finish": [(980, 990)]}
    raw = pt.ProgramTrace(program, {0: pt.ScopedOps(names, start, end,
                                                    scopes)})
    return summary, pt.Program(summary, raw)


def view(summary, program):
    host = {"solves": [dict(rounds=3, lanes=4, nodes=40, traced=True)],
            "steps": 2, "lanes_per_chip": 4}
    v = harness.View(host, summary)
    v.program = program
    return v


def test_a_loop_covering_two_copies_keeps_only_its_own_time():
    # while [0, 100) covers copy [10, 40) and copy [60, 90); a fusion
    # [100, 120) follows; a second loop nests one op in another.
    start = np.array([0, 10, 60, 100, 200, 210, 220], np.int64)
    end = np.array([100, 40, 90, 120, 300, 290, 230], np.int64)
    s, e, op = pt.self_segments(start, end)
    own = np.zeros(len(start), np.int64)
    np.add.at(own, op, e - s)
    assert own.tolist() == [40, 30, 30, 20, 20, 70, 10]
    assert int((e - s).sum()) == tr.covered(*tr.union(start, end), 0, 400)


def test_scope_self_times_and_the_unscoped_part_add_up_to_busy_time():
    summary, program = trace()
    assert summary.busy_ns() == 3 * 130
    assert program.scope_ns(["engine.evaluate"]) == 3 * 20
    assert program.scope_ns(["engine.select", "engine.advance"]) == 3 * 40
    assert program.scope_ns(["steal.balance_device"]) == 3 * 20
    assert program.unscoped_ns() == 3 * 40          # the loop's own time
    parts = sum(program.scope_ns([s]) for s in program.scopes if s)
    assert parts + program.unscoped_ns() == summary.busy_ns()
    # Inside an interval: the second round alone.
    assert program.scope_ns(["engine.evaluate"], 400, 700) == 20
    assert program.unscoped_in_program_ns() == 3 * 40
    top = program.top_self_ops(2)
    assert top[0] == ["while.1", "", pytest.approx(120e-9)]
    assert ["copy.2", "steal.balance_device",
            pytest.approx(30e-9)] in program.top_self_ops(10)


def test_an_idle_gap_is_named_by_the_innermost_program_span():
    _, program = trace()
    assert program.host_label(5) == "harness"
    assert program.host_label(50) == "repro.solve.prepare"
    assert program.host_label(150) == "repro.round.trace"
    assert program.host_label(390) == "repro.solve.readback"
    assert program.host_label(600) == "repro.solve.round"
    assert program.host_label(985) == "repro.solve.finish"
    # [0, 250) opens before the solve, [580, 750) in the second round,
    # [880, 1000) in the third, [380, 450) in the first round's readback.
    assert program.idle_gaps(4) == [
        ["harness", 250e-9], ["repro.solve.round", 170e-9],
        ["repro.solve.round", 120e-9], ["repro.solve.readback", 70e-9]]


def test_the_new_readers_on_a_synthetic_trace():
    v = view(*trace())
    read = {name: harness.reader(name).read(v) for name in NEW_READERS}
    lane_steps = 3 * 2 * 4
    assert read["prepare_ms.solve"] == pytest.approx(90 / 1e6)
    assert read["first_dispatch_ms.solve"] == pytest.approx(150 / 1e6)
    assert read["round_traces.solve"] == 1
    # Rounds 2 and 3: 300 and 280 ns long, 130 busy each.
    assert read["host_us_per_round.solve"] == pytest.approx(
        (170 + 150) / 2 / 1e3)
    assert read["steal_us_per_round.solve"] == pytest.approx(60 / 3 / 1e3)
    assert read["evaluate_ns_per_lane_step.solve"] == pytest.approx(
        60 / lane_steps)
    assert read["select_advance_ns_per_lane_step.solve"] == pytest.approx(
        120 / lane_steps)
    assert read["unscoped_share.solve"] == pytest.approx(100 * 120 / 390)
    # The parts per lane-step add up to the whole step's device time.
    device = harness.reader("device_ns_per_lane_step.solve").read(v)
    open_work = 30 / lane_steps
    unscoped = read["unscoped_share.solve"] / 100 * device
    assert (read["evaluate_ns_per_lane_step.solve"]
            + read["select_advance_ns_per_lane_step.solve"]
            + read["steal_us_per_round.solve"] * 1e3 * 3 / lane_steps
            + unscoped + open_work) == pytest.approx(device)


@pytest.mark.parametrize("scoped,program_spans", [(False, False),
                                                  (True, False),
                                                  (False, True)])
def test_a_program_without_the_names_reads_nothing_new(scoped,
                                                       program_spans):
    v = view(*trace(scoped, program_spans))
    for name in NEW_READERS:
        value = harness.reader(name).read(v)
        needs = program_spans if name in NEW_READERS[:4] else scoped
        assert (value is None) == (not needs), name
    assert harness.reader("unscoped_share.solve").read(
        view(*trace(False, True))) is None


def test_the_new_readers_read_nothing_without_a_trace():
    for name in NEW_READERS:
        assert harness.reader(name).read(harness.View({}, None)) is None


def test_the_existing_readers_read_the_same_beside_the_program_names():
    with_names, without = view(*trace()), view(*trace(False, False))
    for name in OLD_READERS:
        assert harness.reader(name).read(with_names) == \
            harness.reader(name).read(without), name
    assert harness.reader("device_ns_per_lane_step.solve").read(
        with_names) == pytest.approx(390 / 24)
    assert harness.reader("entry_ms.solve").read(with_names) == \
        pytest.approx(240 / 1e6)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(round_fn)/while/body/engine.advance/sel:select", "engine.advance"),
    ("jit(round_fn)/shard_map/vmap(engine.select)", "engine.select"),
    ("jit(round_fn)/steal.balance_device/engine.select/x:x", "engine.select"),
    ("jit(round_fn)/steal.balance_device/vmap()/while:",
     "steal.balance_device"),
    ("jit(round_fn)/while:", ""),
    ("", ""),
])
def test_an_ops_scope_is_the_innermost_in_its_op_name(op_name, scope):
    assert pt.scope_of(op_name) == scope


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of (number, int | str | bytes | float) fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, float):
            out += _varint(number << 3 | 1) + np.float64(value).tobytes()
        elif isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(data)) + data
    return out


def _plane(name, events, stat_names):
    """An XPlane: a line of events, event metadata (id, name, stats) and
    stat metadata (id, name)."""
    fields = [(1, 7), (2, name), (3, _msg((2, "XLA Ops"), (4, _msg((1, 1)))))]
    for i, (event, stats) in enumerate(events, 1):
        fields.append((4, _msg((1, i), (2, _msg(
            (1, i), (2, event), (4, event.split(" ")[0]),
            *[(5, _msg(*stat)) for stat in stats])))))
    for i, stat in stat_names.items():
        fields.append((5, _msg((1, i), (2, _msg((1, i), (2, stat))))))
    return _msg(*fields)


def test_op_names_come_from_the_event_metadata_of_the_device_planes():
    stats = {7: "tf_op", 8: "flops", 9: "jit(round_fn)/steal.balance_device/"
                                        "vmap()/while:"}
    device = _plane("/device:TPU:0", [
        ("%fusion.1 = u32[4] fusion()", [
            [(1, 8), (2, 1.5)],
            [(1, 7), (5, "jit(round_fn)/while/body/engine.evaluate/x:x")]]),
        ("%copy.101 = u32[4] copy()", [[(1, 7), (7, 9)]]),  # interned
        ("%while.46 = (u32[4]) while()", [[(1, 8), (4, 12)]]),
    ], stats)
    host = _plane("/host:CPU", [("%fusion.1 = u32[4] fusion()",
                                 [[(1, 7), (5, "elsewhere")]])], stats)
    space = _msg((1, device), (1, host), (2, "an error"))
    assert pt.op_names(space, ["/device:TPU:0"]) == {
        "%fusion.1 = u32[4] fusion()":
            "jit(round_fn)/while/body/engine.evaluate/x:x",
        "%copy.101 = u32[4] copy()":
            "jit(round_fn)/steal.balance_device/vmap()/while:"}
    assert pt.op_names(space, ["/device:TPU:1"]) == {}


def test_load_reads_the_programs_host_spans_from_a_real_trace(tmp_path):
    import jax
    from repro import registry
    from repro.solver import Solver, SolverConfig

    solver = Solver(SolverConfig(lanes=8, steps_per_round=16))
    with jax.profiler.trace(str(tmp_path)):
        res = solver.solve(registry.problem("vc", "gnp:14:30:5"))
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    raw = pt.load(path, [0])
    assert len(raw.spans["repro.solve.round"]) == res.stats.rounds
    assert len(raw.spans["repro.solve.prepare"]) == 1
    assert len(raw.spans["repro.round.trace"]) == 1
    assert "harness.window" not in raw.spans
