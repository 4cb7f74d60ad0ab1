"""Device-idle time inside the ``repro.solve.round`` spans of the traced
solves, after each solve's first round (which holds the round's trace and
compile), over those rounds: the host's gap in a round (dispatch, the
open-work readback, the loop).  us.  Moves ``solve_s``."""

import program_trace


def read(view):
    program = program_trace.of(view)
    if program is None:
        return None
    idle, rounds = 0.0, 0
    for lo, hi in program_trace.traced_solves(view):
        for s, e in program.inside("repro.solve.round", lo, hi)[1:]:
            idle += (e - s) - view.trace.busy_ns(s, e)
            rounds += 1
    return idle / rounds / 1e3 if rounds else None
