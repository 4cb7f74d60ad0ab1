"""Profiler spans and scopes: the solver's phases, by name, in the trace
that ``jax.profiler`` records (DESIGN.md §8).

Two kinds of name, each from a frozen table:

* :func:`span` — a host span, a ``jax.profiler.TraceAnnotation`` on the
  host plane of the trace, around host work of the driver.  Names in
  :data:`SPAN_NAMES`.
* :func:`scope` — a device scope, a ``jax.named_scope`` around traced
  code.  It adds the name to the ``op_name`` metadata of every op the
  code lowers to, so each device op of the trace names its phase.  Names
  in :data:`SCOPE_NAMES`.

The profiler's trace is the store and the clock: host spans and device
ops share it, so an idle gap of the device can be put down to the host
span open around it.  Nothing here times anything or keeps a record.
Spans and scopes are always on: a span costs about a microsecond when no
profiler records, and a scope changes metadata only.  An unknown name
raises, as an unknown event kind does (``solver.EVENT_KINDS``).
"""

from __future__ import annotations

import jax

__all__ = ["SCOPE_NAMES", "SPAN_NAMES", "scope", "span"]

#: Host spans of the solve driver, and the trace-time span of the round.
SPAN_NAMES = frozenset({
    "repro.solve.prepare",    # resolve, build and jit the round, lanes
    "repro.solve.round",      # one dispatch: feed, dispatch, readback, hooks
    "repro.solve.dispatch",   # the call of the jitted round loop
    "repro.solve.readback",   # the open-work sync that ends the dispatch
    "repro.solve.finish",     # the stats and payload readbacks
    "repro.round.trace",      # the round's Python body, run when traced
})

#: Device scopes of the round and its engine step.
SCOPE_NAMES = frozenset({
    "engine.select",          # read each lane's node off its stack
    "engine.evaluate",        # evaluate every lane's node
    "engine.advance",         # descend or backtrack, write the stacks
    "engine.elect",           # per-instance incumbent election
    "steal.balance_device",   # the steal between lanes of one chip
    "steal.cross_device",     # the steal across chips
    "round.share_best",       # the incumbent's min across chips
    "round.open_work",        # the per-instance open-work count
})


def _known(name: str, table: frozenset, what: str) -> None:
    if name not in table:
        raise ValueError(f"unknown {what} {name!r} (known: "
                         f"{', '.join(sorted(table))})")


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of :data:`SPAN_NAMES`)."""
    _known(name, SPAN_NAMES, "span")
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """A device scope named ``name`` (one of :data:`SCOPE_NAMES`)."""
    _known(name, SCOPE_NAMES, "scope")
    return jax.named_scope(name)
