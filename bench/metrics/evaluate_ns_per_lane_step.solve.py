"""Device self time under the ``engine.evaluate`` scope in the traced
solves, over their lane-steps (rounds x steps per round x lanes per chip,
as ``device_ns_per_lane_step.solve``): the evaluation of one lane's node.
ns.  Moves ``solve_s``."""

import program_trace


def read(view):
    program = program_trace.of(view)
    if program is None or not program.has_scopes:
        return None
    solves = program_trace.traced_solves(view)
    steps = program_trace.lane_steps(view)
    if not solves or not steps:
        return None
    return sum(program.scope_ns(["engine.evaluate"], lo, hi)
               for lo, hi in solves) / steps
