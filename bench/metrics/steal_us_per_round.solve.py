"""Device self time under the ``steal.balance_device`` scope in the traced
solves, over their rounds: the steal between the lanes of one chip.  us.
Moves ``solve_s``."""

import program_trace


def read(view):
    program = program_trace.of(view)
    if program is None or not program.has_scopes:
        return None
    solves = program_trace.traced_solves(view)
    rounds = program_trace.traced_rounds(view)
    if not solves or not rounds:
        return None
    return sum(program.scope_ns(["steal.balance_device"], lo, hi)
               for lo, hi in solves) / rounds / 1e3
