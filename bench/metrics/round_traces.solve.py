"""``repro.round.trace`` spans per traced solve: how many times the round's
Python body was traced in one call (the span opens only while JAX traces
it).  Moves ``solve_s``."""

import program_trace


def read(view):
    program = program_trace.of(view)
    if program is None or not program.has_spans:
        return None
    solves = program_trace.traced_solves(view)
    if not solves:
        return None
    return sum(len(program.inside("repro.round.trace", lo, hi))
               for lo, hi in solves) / len(solves)
