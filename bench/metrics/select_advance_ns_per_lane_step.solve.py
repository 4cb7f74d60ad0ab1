"""Device self time under the ``engine.select``, ``engine.advance`` and
``engine.elect`` scopes in the traced solves, over their lane-steps (as
``device_ns_per_lane_step.solve``): reading each lane's node off its
stack, descending or backtracking, and the incumbent election.  ns.  Moves
``solve_s``."""

import program_trace

SCOPES = ("engine.select", "engine.advance", "engine.elect")


def read(view):
    program = program_trace.of(view)
    if program is None or not program.has_scopes:
        return None
    solves = program_trace.traced_solves(view)
    steps = program_trace.lane_steps(view)
    if not solves or not steps:
        return None
    return sum(program.scope_ns(SCOPES, lo, hi)
               for lo, hi in solves) / steps
