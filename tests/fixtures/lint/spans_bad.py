"""Known-bad fixture for the telemetry-schema rule's profiler names:
span and scope names that are not in SPAN_NAMES / SCOPE_NAMES, in each
import form."""
from repro import obs
from repro.obs import spans
from repro.obs.spans import scope, span as host_span


def drive(fn, x):
    with obs.span("repro.solve.warp"):          # BAD: not in SPAN_NAMES
        x = fn(x)
    with host_span("solve.round"):              # BAD: not in SPAN_NAMES
        x = fn(x)
    with obs.scope("repro.solve.round"):        # BAD: a span, not a scope
        x = fn(x)
    with scope("engine.expand"):                # BAD: not in SCOPE_NAMES
        x = fn(x)
    with spans.scope("steal.global"):           # BAD: not in SCOPE_NAMES
        x = fn(x)
    return x
