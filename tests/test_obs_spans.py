"""Profiler spans and scopes (repro.obs.spans): names come from frozen
tables, ``Solver.solve`` writes its phases as host spans into the
profiler's trace, and the lowered round names its phases in the
``op_name`` metadata of its ops under the program name ``jit_round_fn``.
"""

import collections
import glob
import json
import os
import re
import subprocess
import sys

import jax
import pytest
from jax.profiler import ProfileData

from repro import obs, registry
from repro.core.distributed import make_round
from repro.core.engine import init_lanes
from repro.solver import Solver, SolverConfig

VC = registry.problem("vc", "gnp:14:30:5")
#: Scopes that exist only on a mesh (collectives across chips).
MESH_SCOPES = {"steal.cross_device", "round.share_best"}


def test_unknown_span_or_scope_name_raises():
    with pytest.raises(ValueError, match="unknown span"):
        obs.span("repro.solve.warp")
    with pytest.raises(ValueError, match="unknown scope"):
        obs.scope("engine.expand")
    # A span's name is not a scope's, nor the other way round.
    with pytest.raises(ValueError):
        obs.scope("repro.solve.round")
    with pytest.raises(ValueError):
        obs.span("engine.select")
    for name in obs.SPAN_NAMES:
        with obs.span(name):
            pass
    for name in obs.SCOPE_NAMES:
        with obs.scope(name):
            pass


def _host_spans(logdir):
    """name -> [(start, end)] of the ``repro.`` host events of a trace."""
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    spans = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return spans


@pytest.mark.parametrize("bootstrap_rounds", [0, 2])
def test_solve_writes_its_phases_into_the_profiler_trace(tmp_path,
                                                         bootstrap_rounds):
    solver = Solver(SolverConfig(lanes=8, steps_per_round=16,
                                 bootstrap_rounds=bootstrap_rounds,
                                 metrics=True))
    with jax.profiler.trace(str(tmp_path)):
        res = solver.solve(VC)
    spans = _host_spans(tmp_path)
    rounds = res.stats.rounds
    assert rounds > 1
    assert len(spans["repro.solve.round"]) == rounds
    assert len(spans["repro.solve.dispatch"]) == rounds
    assert len(spans["repro.solve.readback"]) == rounds
    for name in ("repro.solve.prepare", "repro.solve.finish"):
        assert len(spans[name]) == 1, name
    # One trace of each round program: the main round, and the bootstrap
    # round when there is one.
    traces = 1 + (bootstrap_rounds > 0)
    assert len(spans["repro.round.trace"]) == traces
    assert solver.metrics().value("round_traces") == traces

    # Nesting: prepare, then the rounds, then finish; each dispatch and
    # readback inside a round, the trace inside the first dispatch.
    (p0, p1), = spans["repro.solve.prepare"]
    (f0, f1), = spans["repro.solve.finish"]
    round_spans = sorted(spans["repro.solve.round"])
    assert p1 <= round_spans[0][0] and round_spans[-1][1] <= f0
    for name in ("repro.solve.dispatch", "repro.solve.readback"):
        for s, e in spans[name]:
            assert any(r0 <= s and e <= r1 for r0, r1 in round_spans), name
    first_dispatch = min(spans["repro.solve.dispatch"])
    t0, t1 = min(spans["repro.round.trace"])
    assert first_dispatch[0] <= t0 and t1 <= first_dispatch[1]


@pytest.mark.parametrize("bootstrap_rounds", [0, 2])
def test_a_plain_solve_is_one_dispatch_and_one_more_for_its_bootstrap(
        tmp_path, bootstrap_rounds):
    """With nothing that takes each round back, every main round runs in
    one dispatch of the device loop, and the bootstrap rounds in one
    more; each dispatch has its ``repro.solve.round`` span."""
    solver = Solver(SolverConfig(lanes=8, steps_per_round=16,
                                 bootstrap_rounds=bootstrap_rounds))
    with jax.profiler.trace(str(tmp_path)):
        res = solver.solve(registry.problem("vc", "gnp:30:20:3"))
    spans = _host_spans(tmp_path)
    assert res.stats.rounds > bootstrap_rounds + 1
    dispatches = 1 + (bootstrap_rounds > 0)
    for name in ("repro.solve.round", "repro.solve.dispatch",
                 "repro.solve.readback", "repro.round.trace"):
        assert len(spans[name]) == dispatches, name


def test_round_traces_counts_each_call_and_only_with_metrics():
    solver = Solver(SolverConfig(lanes=8, steps_per_round=16, metrics=True))
    for _ in range(2):
        # Every call builds and traces its round anew.
        solver.solve(VC)
        assert solver.metrics().value("round_traces") == 1
    assert Solver(SolverConfig(lanes=8, steps_per_round=16)).metrics() is None


def _scopes_and_program(compiled_text):
    names = set(re.findall(r'op_name="([^"]*)"', compiled_text))
    found = {s for s in obs.SCOPE_NAMES
             if any(re.search(rf"(^|/){re.escape(s)}(/|$)", n)
                    for n in names)}
    program = re.match(r"HloModule (\S+?),", compiled_text).group(1)
    return found, program


def test_one_device_round_names_its_phases_and_its_program():
    prob = VC.build()
    text = jax.jit(make_round(prob, 4)).lower(
        init_lanes(prob, 8)).compile().as_text()
    found, program = _scopes_and_program(text)
    assert found == obs.SCOPE_NAMES - MESH_SCOPES
    assert program == "jit_round_fn"


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import jax
from repro import obs, registry
from repro.core.distributed import _shard_lanes, make_distributed_round
from repro.core.engine import init_lanes

assert len(jax.devices()) == 4, jax.devices()
prob = registry.problem("vc", "gnp:14:30:5").build()
mesh = jax.make_mesh((4,), ("workers",))
lanes = _shard_lanes(init_lanes(prob, 4 * 8), mesh)
print(json.dumps(make_distributed_round(prob, mesh, 4).lower(
    lanes).compile().as_text()))
"""


def test_mesh_round_names_every_phase_and_keeps_its_program_name():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    text = json.loads(proc.stdout.strip().splitlines()[-1])
    found, program = _scopes_and_program(text)
    assert found == obs.SCOPE_NAMES
    assert program == "jit_round_fn"
