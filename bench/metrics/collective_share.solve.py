"""Self time of the collective ops (all-reduce, all-gather,
collective-permute, all-to-all, reduce-scatter, and their async starts and
ends) over device busy time in the traced solves, mean over the cell's
chips: the time the mesh spends in collectives that no other op of its
chip overlaps.  An op is a collective by its HLO opcode (``%pmin.14 =
s32[1]{0} all-reduce(...)`` is one), or by its name where the trace gives
no HLO text.  %.  Moves ``solve_s``."""

import re

import numpy as np

import program_trace
import trace_reduce as tr

#: The opcode of an op's HLO text: the first ``word(`` after its ``=``.
OPCODE = re.compile(r"=.*?\s([a-z][\w-]*)\(")


def is_collective(name: str) -> bool:
    m = OPCODE.search(name)
    return bool(tr.COLLECTIVE.match(m.group(1) if m else tr.short_name(name)))


def read(view):
    program = program_trace.of(view)
    if program is None:
        return None
    solves = program_trace.traced_solves(view)
    busy = sum(view.trace.busy_ns(lo, hi) for lo, hi in solves)
    if not busy:
        return None
    per_chip = []
    # The self segments the program's reduction has already cut (a pass in
    # Python over every op of the trace, too dear to make twice).
    for d, (s, e, op, _) in program._segments.items():
        names = program.raw.ops[d].names
        kinds = {n: is_collective(n) for n in set(names)}
        keep = np.fromiter((kinds[n] for n in names), bool, len(names))[op]
        per_chip.append(sum(tr.covered(s[keep], e[keep], max(lo, program.lo),
                                       min(hi, program.hi))
                            for lo, hi in solves))
    ns = float(np.mean(per_chip))
    return 100.0 * ns / busy if ns else None
