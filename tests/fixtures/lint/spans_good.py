"""Known-good fixture for the telemetry-schema rule's profiler names:
known span and scope names in each import form, variable names skipped,
and same-named helpers of other modules out of scope."""
from repro import obs
from repro.obs import spans
from repro.obs.spans import scope, span as host_span


def drive(fn, x, name, other):
    with obs.span("repro.solve.round"):
        with host_span("repro.solve.dispatch"):
            x = fn(x)
    with scope("engine.evaluate"), spans.scope("steal.balance_device"):
        x = fn(x)
    with obs.span(name):                     # variable name: runtime's job
        x = fn(x)
    with other.span("anything"), other.scope("goes"):
        x = fn(x)
    return x
