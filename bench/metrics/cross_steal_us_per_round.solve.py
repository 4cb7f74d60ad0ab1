"""Device self time under the ``steal.cross_device`` scope in the traced
solves, over their rounds, mean over the cell's chips: the steal between
chips (advertising demand and supply, extracting, shipping and installing
tasks).  us.  Moves ``solve_s``."""

import program_trace


def read(view):
    program = program_trace.of(view)
    if program is None or not program.has_scopes:
        return None
    solves = program_trace.traced_solves(view)
    rounds = program_trace.traced_rounds(view)
    if not solves or not rounds:
        return None
    ns = sum(program.scope_ns(["steal.cross_device"], lo, hi)
             for lo, hi in solves)
    return ns / rounds / 1e3 if ns else None
