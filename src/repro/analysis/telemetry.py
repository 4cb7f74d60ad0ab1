"""telemetry-schema: emit()/trace-write/span call sites checked statically.

PR 7's runtime validation (``solver.emit`` + ``TraceWriter.write`` both
raise on unknown kinds / missing fields) only fires when the offending
code path executes — a typo'd lifecycle kind in a rarely-taken branch
ships silently.  This rule resolves every call site with a *literal*
kind string against the same ground-truth tables the runtime uses:

  * ``EVENT_KINDS``  — AST-extracted from ``src/repro/solver.py``;
  * ``TRACE_KINDS``  — AST-extracted from ``src/repro/obs/trace.py``
    (kind -> required-field frozenset).

Checked shapes (kinds that are variables are skipped — the runtime
validator still covers them):

  * ``emit(cb, "kind", ...)`` and method-style ``self._emit("kind",
    ...)`` / ``obj.emit("kind", ...)``  -> kind ∈ EVENT_KINDS;
  * ``ProgressEvent(kind="kind", ...)`` -> kind ∈ EVENT_KINDS;
  * ``<trace-ish receiver>.write("kind", field=..., ...)`` -> kind ∈
    TRACE_KINDS and required fields ⊆ keyword names (unless ``**kw`` is
    forwarded).  "Trace-ish" = the receiver expression mentions
    ``trace`` (``self.trace``, ``trace``, ``self._trace`` ...), which
    keeps ordinary file ``.write()`` calls out of scope;
  * ``obj.lifecycle("kind", ...)`` -> kind ∈ TRACE_KINDS (the
    collector renames ``round_no``->``round``, so only membership is
    checked here);
  * ``obs.span("name")`` / ``obs.scope("name")``, and ``span``/``scope``
    imported from ``repro.obs`` or ``repro.obs.spans`` -> name ∈
    ``SPAN_NAMES`` / ``SCOPE_NAMES`` (AST-extracted from
    ``src/repro/obs/spans.py``).

The tables are read from the analyzed module set first (so editing
``solver.py`` and linting ``src`` sees the edited table) and fall back
to the checkout this package lives in (so fixture runs resolve too).
"""

from __future__ import annotations

import ast
from typing import List, Optional

from repro.analysis.core import Finding, Module, RepoContext, Rule, register

_EVENT_TABLE = ("src/repro/solver.py", "EVENT_KINDS")
_TRACE_TABLE = ("src/repro/obs/trace.py", "TRACE_KINDS")
_SPANS_MODULE = "src/repro/obs/spans.py"
#: profiler helper -> the table of its names in ``_SPANS_MODULE``.
_NAME_TABLES = {"span": "SPAN_NAMES", "scope": "SCOPE_NAMES"}
_OBS_MODULES = ("repro.obs", "repro.obs.spans")


def _literal_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _profiler_aliases(tree) -> tuple:
    """(module aliases of ``repro.obs``/``repro.obs.spans``, bare-name
    alias -> ``span``/``scope``) bound by the module's imports."""
    modules, names = set(), {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name in _OBS_MODULES and alias.asname:
                    modules.add(alias.asname)
        elif isinstance(node, ast.ImportFrom):
            for alias in node.names:
                asname = alias.asname or alias.name
                if f"{node.module}.{alias.name}" in _OBS_MODULES:
                    modules.add(asname)
                elif node.module in _OBS_MODULES and \
                        alias.name in _NAME_TABLES:
                    names[asname] = alias.name
    return modules, names


def _expr_mentions_trace(node) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and "trace" in n.id.lower():
            return True
        if isinstance(n, ast.Attribute) and "trace" in n.attr.lower():
            return True
    return False


@register
class TelemetrySchemaRule(Rule):
    name = "telemetry-schema"
    description = ("emit()/trace write() call sites must use known "
                   "EVENT_KINDS/TRACE_KINDS with required fields; "
                   "obs.span()/obs.scope() names known SPAN_NAMES/"
                   "SCOPE_NAMES")
    severity = "error"

    def run(self, ctx: RepoContext) -> List[Finding]:
        event_kinds = ctx.literal(*_EVENT_TABLE)
        trace_kinds = ctx.literal(*_TRACE_TABLE)
        if not isinstance(event_kinds, (set, frozenset)):
            event_kinds = None
        if not isinstance(trace_kinds, dict):
            trace_kinds = None

        tables = {}
        for helper, table in _NAME_TABLES.items():
            known = ctx.literal(_SPANS_MODULE, table)
            if isinstance(known, (set, frozenset)):
                tables[helper] = (table, known)

        findings: List[Finding] = []
        for mod in ctx.modules:
            if mod.rel in (_EVENT_TABLE[0], _TRACE_TABLE[0], _SPANS_MODULE):
                continue     # the tables' own modules define the schema
            aliases = _profiler_aliases(mod.tree)
            for call in ast.walk(mod.tree):
                if not isinstance(call, ast.Call):
                    continue
                self._check_call(mod, call, event_kinds, trace_kinds,
                                 findings)
                self._check_name(mod, call, aliases, tables, findings)
        return findings

    def _check_name(self, mod: Module, call: ast.Call, aliases, tables,
                    findings: List[Finding]) -> None:
        """``span("name")``/``scope("name")``: the name is in its table."""
        modules, names = aliases
        func, helper = call.func, None
        if isinstance(func, ast.Name):
            helper = names.get(func.id)
        elif isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name) and \
                func.value.id in modules and func.attr in _NAME_TABLES:
            helper = func.attr
        if helper not in tables or not call.args:
            return
        name = _literal_str(call.args[0])
        table, known = tables[helper]
        if name is not None and name not in known:
            f = self.finding(mod, call,
                             f"unknown {helper} name {name!r} — not in "
                             f"obs.spans.{table} "
                             f"({', '.join(sorted(known))})")
            if f:
                findings.append(f)

    def _check_call(self, mod: Module, call: ast.Call, event_kinds,
                    trace_kinds, findings: List[Finding]) -> None:
        func = call.func

        def add(message):
            f = self.finding(mod, call, message)
            if f:
                findings.append(f)

        # -- emit(...) ----------------------------------------------------
        kind = None
        if isinstance(func, ast.Name) and func.id == "emit":
            if len(call.args) >= 2:
                kind = _literal_str(call.args[1])
        elif isinstance(func, ast.Attribute) and \
                func.attr in ("emit", "_emit"):
            if call.args:
                kind = _literal_str(call.args[0])
        elif (isinstance(func, ast.Name) and func.id == "ProgressEvent"):
            for kw in call.keywords:
                if kw.arg == "kind":
                    kind = _literal_str(kw.value)
            if kind is None and call.args:
                kind = _literal_str(call.args[0])
        if kind is not None and event_kinds is not None:
            if kind not in event_kinds:
                add(f"unknown progress-event kind {kind!r} — not in "
                    f"solver.EVENT_KINDS "
                    f"({', '.join(sorted(event_kinds))})")
            return
        if kind is not None:
            return

        # -- trace.write(...) / lifecycle(...) ----------------------------
        if not isinstance(func, ast.Attribute):
            return
        if func.attr == "write" and _expr_mentions_trace(func.value):
            if not call.args:
                return
            kind = _literal_str(call.args[0])
            if kind is None or trace_kinds is None:
                return
            if kind not in trace_kinds:
                add(f"unknown trace record kind {kind!r} — not in "
                    f"obs.trace.TRACE_KINDS "
                    f"({', '.join(sorted(trace_kinds))})")
                return
            has_star_kwargs = any(kw.arg is None for kw in call.keywords)
            if has_star_kwargs:
                return
            given = {kw.arg for kw in call.keywords}
            required = trace_kinds[kind]
            missing = sorted(set(required) - given)
            if missing:
                add(f"trace record {kind!r} is missing required "
                    f"field(s) {missing} (TRACE_KINDS[{kind!r}] = "
                    f"{{{', '.join(sorted(required))}}})")
        elif func.attr == "lifecycle":
            if not call.args:
                return
            kind = _literal_str(call.args[0])
            if kind is None or trace_kinds is None:
                return
            if kind not in trace_kinds:
                add(f"unknown lifecycle kind {kind!r} — not in "
                    f"obs.trace.TRACE_KINDS")
