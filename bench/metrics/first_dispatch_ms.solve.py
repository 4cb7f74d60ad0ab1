"""Duration of each traced solve's first ``repro.solve.dispatch`` span,
mean over the traced solves: tracing and lowering the round, fetching it
from the compile cache or compiling it, and the launch.  ms.  Moves
``solve_s``."""

import program_trace


def read(view):
    program = program_trace.of(view)
    if program is None:
        return None
    firsts = []
    for lo, hi in program_trace.traced_solves(view):
        dispatches = program.inside("repro.solve.dispatch", lo, hi)
        if dispatches:
            s, e = dispatches[0]
            firsts.append(e - s)
    return sum(firsts) / len(firsts) / 1e6 if firsts else None
