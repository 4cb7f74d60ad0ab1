"""Share of the device's busy time in the traced solves that no
``engine.*``, ``steal.*`` or ``round.*`` scope claims (self time of ops
without a scope, over busy time): what the per-phase metrics cannot place.
%.  Moves ``solve_s``."""

import program_trace


def read(view):
    program = program_trace.of(view)
    if program is None or not program.has_scopes:
        return None
    solves = program_trace.traced_solves(view)
    busy = sum(view.trace.busy_ns(lo, hi) for lo, hi in solves)
    if not busy:
        return None
    return 100.0 * sum(program.unscoped_ns(lo, hi)
                       for lo, hi in solves) / busy
