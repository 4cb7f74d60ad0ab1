"""api-hygiene: snapshot-guarded exports + well-formed deprecations.

Two clauses:

1. **Exports are snapshotted.**  For every front-door module listed in
   ``tools/api_surface.py``'s ``MODULES`` tuple, each name in the
   module's ``__all__`` must appear in the checked-in snapshot
   ``tools/api_surface.txt`` (under that module's section).  This is
   the static half of the snapshot guard: ``api_surface.py --check``
   catches *drift* at docs-smoke time but needs a working import of
   jax; this rule catches a forgotten snapshot regen with no deps at
   all, at lint time.  Both ``MODULES`` and ``__all__`` are resolved
   from the AST, never imported.
2. **Deprecation shims use the exactly-once pattern.**  Every
   ``warnings.warn(..., DeprecationWarning, ...)`` must pass
   ``stacklevel=2`` (point at the *caller*, which is what lets
   ``tests/_legacy.one_deprecation`` and the pytest.ini error filters
   pin each shim exactly once) and, when the message is a literal,
   say "deprecated" so the filter regexes can match it.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set

from repro.analysis.core import Finding, Module, RepoContext, Rule, register

_SNAPSHOT_ENTRY = re.compile(
    r"^  (?:def|const|dataclass|namedtuple|class)\s+([A-Za-z_][A-Za-z_0-9]*)")


def _parse_snapshot(text: str) -> Dict[str, Set[str]]:
    """api_surface.txt -> {module: {exported names}}."""
    sections: Dict[str, Set[str]] = {}
    current: Optional[str] = None
    for line in text.splitlines():
        if line.startswith("module "):
            current = line[len("module "):].strip()
            sections[current] = set()
        elif current is not None:
            m = _SNAPSHOT_ENTRY.match(line)
            if m:
                sections[current].add(m.group(1))
    return sections


@register
class ApiHygieneRule(Rule):
    name = "api-hygiene"
    description = ("public exports snapshotted in tools/api_surface.txt; "
                   "deprecation shims use the exactly-once pattern")
    severity = "error"

    def run(self, ctx: RepoContext) -> List[Finding]:
        findings: List[Finding] = []
        self._check_snapshot(ctx, findings)
        for mod in ctx.modules:
            self._check_deprecations(mod, findings)
        return findings

    # -- clause 1: exports ⊆ snapshot ------------------------------------

    def _check_snapshot(self, ctx: RepoContext,
                        findings: List[Finding]) -> None:
        modules = ctx.literal("tools/api_surface.py", "MODULES")
        snapshot_text = ctx.read("tools/api_surface.txt")
        if not isinstance(modules, tuple) or snapshot_text is None:
            return
        sections = _parse_snapshot(snapshot_text)
        for dotted in modules:
            # Only check modules present in the analyzed set — a
            # fixture/subset run must not re-audit the whole tree.
            mod = ctx.by_dotted.get(dotted)
            if mod is None:
                continue
            exported = self._module_all(mod)
            if exported is None:
                f = self.finding(
                    mod, 1,
                    f"front-door module {dotted} has no literal "
                    "__all__ — the api-surface snapshot needs one")
                if f:
                    findings.append(f)
                continue
            known = sections.get(dotted)
            if known is None:
                f = self.finding(
                    mod, 1,
                    f"module {dotted} is in api_surface.MODULES but has "
                    "no section in tools/api_surface.txt — run "
                    "`python tools/api_surface.py --update`")
                if f:
                    findings.append(f)
                continue
            for name, lineno in sorted(exported.items()):
                if name not in known:
                    f = self.finding(
                        mod, lineno,
                        f"export {dotted}.{name} is missing from "
                        "tools/api_surface.txt — run `python "
                        "tools/api_surface.py --update` and review "
                        "the diff")
                    if f:
                        findings.append(f)

    @staticmethod
    def _module_all(mod: Module) -> Optional[Dict[str, int]]:
        """``__all__`` names -> line number, or None when absent."""
        for node in mod.tree.body:
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
            else:
                continue
            for tgt in targets:
                if isinstance(tgt, ast.Name) and tgt.id == "__all__":
                    out: Dict[str, int] = {}
                    value = node.value
                    if not isinstance(value, (ast.List, ast.Tuple)):
                        return None
                    for elt in value.elts:
                        if isinstance(elt, ast.Constant) and \
                                isinstance(elt.value, str):
                            out[elt.value] = elt.lineno
                    return out
        return None

    # -- clause 2: deprecation shims -------------------------------------

    def _check_deprecations(self, mod: Module,
                            findings: List[Finding]) -> None:
        for call in ast.walk(mod.tree):
            if not isinstance(call, ast.Call):
                continue
            func = call.func
            is_warn = (
                (isinstance(func, ast.Attribute) and func.attr == "warn"
                 and isinstance(func.value, ast.Name)
                 and func.value.id == "warnings")
                or (isinstance(func, ast.Name) and func.id == "warn"))
            if not is_warn:
                continue
            mentions_dep = any(
                isinstance(n, ast.Name) and n.id == "DeprecationWarning"
                for a in (list(call.args) +
                          [kw.value for kw in call.keywords])
                for n in ast.walk(a))
            if not mentions_dep:
                continue
            stacklevel = None
            if len(call.args) >= 3 and isinstance(call.args[2],
                                                  ast.Constant):
                stacklevel = call.args[2].value
            for kw in call.keywords:
                if kw.arg == "stacklevel" and \
                        isinstance(kw.value, ast.Constant):
                    stacklevel = kw.value.value
            if stacklevel != 2:
                f = self.finding(
                    mod, call,
                    "DeprecationWarning must be raised with "
                    "stacklevel=2 so the warning points at the caller "
                    "(the exactly-once shim pattern pinned by "
                    "pytest.ini / tests/_legacy.py)")
                if f:
                    findings.append(f)
            msg = call.args[0] if call.args else None
            if isinstance(msg, ast.Constant) and \
                    isinstance(msg.value, str) and \
                    "deprecat" not in msg.value.lower():
                f = self.finding(
                    mod, call,
                    "deprecation shim message should say 'deprecated' "
                    "so the pytest.ini error filters can pin it")
                if f:
                    findings.append(f)
