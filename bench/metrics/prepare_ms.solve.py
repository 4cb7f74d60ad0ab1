"""Mean duration of the program's ``repro.solve.prepare`` span over the
traced solves: resolving the problem, building and jitting the round, and
the lanes (``init_lanes``, placement).  ms.  Moves ``solve_s``."""

import program_trace


def read(view):
    program = program_trace.of(view)
    if program is None:
        return None
    spans = program.spans("repro.solve.prepare")
    return sum(e - s for s, e in spans) / len(spans) / 1e6 if spans \
        else None
