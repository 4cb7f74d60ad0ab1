"""Share of the traced window in which no op ran on the device (mean over
the cell's chips), under the ``Solver.solve`` loop.  Moves ``solve_s``."""


def read(view):
    if view.trace is None:
        return None
    return 100.0 * view.trace.idle_share()
