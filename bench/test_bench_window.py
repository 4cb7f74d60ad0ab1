"""The window arithmetic and the readers, on fixed inputs."""

import pytest

import harness


def view(host, trace=None):
    return harness.View(host, trace)


def test_percentile_is_nearest_rank_over_all_values():
    vals = list(range(1, 101))
    assert harness.percentile(vals, 50) == 50
    assert harness.percentile(vals, 95) == 95
    assert harness.percentile([3.0, 1.0, 2.0], 95) == 3.0
    assert harness.percentile([7.0], 50) == 7.0
    with pytest.raises(ValueError):
        harness.percentile([], 50)


def test_lane_occupancy_of_solves_is_nodes_over_offered_lane_steps():
    solves = [dict(rounds=10, lanes=4, nodes=200, traced=False),
              dict(rounds=5, lanes=4, nodes=120, traced=False)]
    read = harness.reader("lane_occupancy.solve").read
    assert read(view({"solves": solves, "steps": 8,
                      "lanes_per_chip": 4})) == pytest.approx(
        100 * 320 / (15 * 8 * 4))


def test_trace_readers_read_nothing_without_a_trace():
    for name in ("idle_share.solve", "entry_ms.solve",
                 "device_ns_per_lane_step.solve"):
        assert harness.reader(name).read(view({})) is None


def test_trace_readers_on_a_synthetic_trace():
    import numpy as np
    import trace_reduce as tr

    spans = {name: [] for name in tr.SPANS}
    spans.update({"harness.window": [(0, 1000)],
                  "service.step_round": [(0, 400), (500, 1000)],
                  "solve": [(0, 400), (500, 1000)]})
    device = tr.Ops(["fusion.1", "all-gather.1", "fusion.2"],
                    np.array([100, 300, 600], np.int64),
                    np.array([300, 350, 900], np.int64))
    programs = tr.Ops(["jit_round_fn(1)", "jit_round_fn(1)"],
                      np.array([100, 600], np.int64),
                      np.array([350, 900], np.int64))
    summary = tr.Summary(tr.RawTrace({0: device}, spans, {0: programs}))
    host = {"solves": [dict(rounds=2, lanes=5, nodes=1, traced=True),
                       dict(rounds=3, lanes=5, nodes=1, traced=True)],
            "steps": 10, "lanes_per_chip": 5}
    v = view(host, summary)
    assert harness.reader("idle_share.solve").read(v) == pytest.approx(45.0)
    assert harness.reader("entry_ms.solve").read(v) == pytest.approx(
        (100 + 100) / 2 / 1e6)
    assert harness.reader("device_ns_per_lane_step.solve").read(v) == \
        pytest.approx(550 / (5 * 10 * 5))
