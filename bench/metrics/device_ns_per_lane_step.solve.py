"""Device busy time inside the traced solves over their lane-steps (rounds x
steps per round x lanes per chip): the cost of one engine step of one
lane, evaluate included, on one chip.  Moves ``solve_s``."""


def read(view):
    if view.trace is None:
        return None
    traced = [s for s in view.host["solves"] if s["traced"]]
    spans = view.trace.spans("solve")
    if not traced or len(spans) != len(traced):
        return None
    busy = sum(view.trace.busy_ns(s, e) for s, e in spans)
    steps = sum(s["rounds"] for s in traced) * view.host["steps"] \
        * view.host["lanes_per_chip"]
    return busy / steps
