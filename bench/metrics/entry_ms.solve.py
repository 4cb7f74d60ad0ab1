"""Time from the start of each traced ``solve`` call to the first run of
its round program (the ``round_fn`` module): the host work before the
search starts, which today is building, tracing and fetching the round
again on every call.  Mean over the traced solves, ms.  Moves
``solve_s``."""

ROUND_PROGRAM = r"round_fn"


def read(view):
    if view.trace is None:
        return None
    waits = []
    for s, e in view.trace.spans("solve"):
        first = view.trace.first_module_after(s, ROUND_PROGRAM)
        if first is not None and first < e:
            waits.append(first - s)
    return sum(waits) / len(waits) / 1e6 if waits else None
