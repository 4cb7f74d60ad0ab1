"""The comparison that decides ``correct``: every answer the window
produced, against the plain reference (``plain_ref.py``).

Each number compared is a count of answers, with the limit 0 (the
comparison is exact):

  missing        due in the window, never answered (open loop, after the
                 bounded drain);
  unfinished     answered without a finished search: a solve that returned
                 with open work, or a request evicted (expired, cancelled);
  wrong_optimum  finished, with a value other than the reference optimum;
  bad_solution   finished, with a solution that is not a vertex cover /
                 dominating set of the very graph submitted, or whose size
                 is not the value returned (this also catches an answer
                 handed to the wrong request).
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional

import numpy as np

import plain_ref

LIMITS = {"missing": 0, "unfinished": 0, "wrong_optimum": 0,
          "bad_solution": 0}


class Answer(NamedTuple):
    key: int                 # which base instance (panel / pool entry)
    family: str              # "vc" | "ds"
    dense: np.ndarray        # the graph as submitted
    base: np.ndarray         # the base instance (same optimum)
    status: str              # "done" | "unproven" | "expired" | ... |
                             # "missing"
    value: Optional[int] = None
    payload: Optional[np.ndarray] = None


class Verdict(NamedTuple):
    correct: bool
    attempted: int
    failed: int
    numbers: Dict[str, int]


def compare(answers: List[Answer]) -> Verdict:
    """Judge ``answers`` (the reference runs once per base instance)."""
    refs: Dict[tuple, int] = {}
    numbers = dict.fromkeys(LIMITS, 0)
    failed = 0
    for a in answers:
        bad = None
        if a.status == "missing":
            bad = "missing"
        elif a.status != "done":
            bad = "unfinished"
        else:
            ref_key = (a.family, a.key)
            if ref_key not in refs:
                refs[ref_key] = plain_ref.OPTIMUM[a.family](a.base)
            if a.value != refs[ref_key]:
                bad = "wrong_optimum"
            elif plain_ref.solution_gap(a.family, a.dense, a.payload,
                                        a.value):
                bad = "bad_solution"
        if bad is not None:
            numbers[bad] += 1
            failed += 1
    correct = bool(answers) and all(numbers[k] <= LIMITS[k]
                                    for k in LIMITS)
    return Verdict(correct, len(answers), failed, numbers)
