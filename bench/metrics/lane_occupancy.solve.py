"""Nodes visited over the lane-steps the rounds offered (rounds x steps per
round x lanes), over every solve of the window: the share of lane-steps
that did work.  A count of the program's, which repeats exactly.  Moves
``solve_s``."""


def read(view):
    solves = view.host["solves"]
    offered = sum(s["rounds"] * view.host["steps"] * s["lanes"]
                  for s in solves)
    return 100.0 * sum(s["nodes"] for s in solves) / offered if offered \
        else None
