"""Lint framework core: findings, rule registry, suppressions, runner.

Design (DESIGN.md §10):

  * A :class:`Rule` is a *project-level* pass — ``run(ctx)`` sees every
    analyzed module at once, because the interesting invariants
    (trace-safety reachability, telemetry schemas) are cross-file.
  * Rules report :class:`Finding` objects (rule id, file, line, message,
    severity).  ``error`` findings fail the build; ``warning`` findings
    are printed but do not affect the exit status.
  * Inline suppressions: ``# repro-lint: disable=<rule> -- <reason>``
    on the offending line (or the line directly above) silences that
    rule for that line.  The reason is mandatory — one without it is
    itself an error (rule ``suppression``), so every deliberate
    violation is documented in place.
  * Every ``.py`` file under the given paths is analyzed; nothing is
    skipped.

Everything here is stdlib-only; rules must not import jax at module
scope (the CI lint job runs without accelerator deps installed).
"""

from __future__ import annotations

import ast
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Sequence, Tuple, Type

__all__ = [
    "Finding",
    "LintResult",
    "Module",
    "RepoContext",
    "Rule",
    "all_rules",
    "lint_paths",
    "register",
]

_SUPPRESS_RE = re.compile(
    r"#\s*repro-lint:\s*disable=([A-Za-z0-9_,-]+)"
    r"(?:\s+--\s+(?P<reason>\S.*))?")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint diagnostic, anchored to a file:line."""

    rule: str
    path: str          # repo-relative, '/'-separated
    line: int
    message: str
    severity: str = "error"    # "error" | "warning"

    def format(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] "
                f"{self.severity}: {self.message}")


class Module:
    """A parsed source file: path, text, AST, and suppression table."""

    def __init__(self, path: pathlib.Path, rel: str, text: str,
                 tree: ast.Module):
        self.path = path
        self.rel = rel
        self.text = text
        self.tree = tree
        self.lines = text.splitlines()
        # line -> (set of rule names or {"*"}, reason or None)
        self.suppressions: Dict[int, Tuple[frozenset, Optional[str]]] = {}
        self._scan_suppressions()

    def _scan_suppressions(self) -> None:
        for lineno, line in enumerate(self.lines, 1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = frozenset(r.strip() for r in m.group(1).split(",")
                              if r.strip())
            self.suppressions[lineno] = (rules, m.group("reason"))

    def suppressed(self, rule: str, line: int) -> bool:
        """True when ``rule`` is disabled on ``line`` (same line or the
        line directly above the reported one)."""
        for cand in (line, line - 1):
            entry = self.suppressions.get(cand)
            if entry and (rule in entry[0] or "*" in entry[0]):
                return True
        return False

    def dotted(self, src_root: pathlib.Path) -> Optional[str]:
        """Module's dotted import name relative to ``src_root`` (the
        directory on ``sys.path``), or None if outside it."""
        try:
            rel = self.path.resolve().relative_to(src_root.resolve())
        except ValueError:
            return None
        parts = list(rel.with_suffix("").parts)
        if parts and parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts) if parts else None


class RepoContext:
    """Everything a rule needs: the analyzed modules plus repo anchors.

    ``repo_root`` is the repository checkout (auto-detected from this
    file's location) so rules can consult ground-truth files —
    ``src/repro/solver.py`` for ``EVENT_KINDS``, ``tools/api_surface.txt``
    for the snapshot — even when linting a subset of paths (fixtures).
    """

    def __init__(self, modules: Sequence[Module],
                 repo_root: Optional[pathlib.Path] = None):
        self.modules = list(modules)
        if repo_root is None:
            # src/repro/analysis/core.py -> repo checkout root
            repo_root = pathlib.Path(__file__).resolve().parents[3]
        self.repo_root = repo_root
        self.src_root = repo_root / "src"
        # The checkout this package lives in — fallback for ground-truth
        # files (schema tables, snapshots) when linting a subtree that
        # does not contain them (e.g. the fixture corpus).
        self.package_root = pathlib.Path(__file__).resolve().parents[3]
        self._file_cache: Dict[str, Optional[str]] = {}
        self.by_dotted: Dict[str, Module] = {}
        for mod in self.modules:
            name = mod.dotted(self.src_root)
            if name:
                self.by_dotted[name] = mod

    def read(self, rel: str) -> Optional[str]:
        """Text of a repo-relative file, or None if absent.  Prefers the
        analyzed module set (so fixture runs see fixture content)."""
        if rel not in self._file_cache:
            for mod in self.modules:
                if mod.rel == rel:
                    self._file_cache[rel] = mod.text
                    break
            else:
                text = None
                for base in (self.repo_root, self.package_root):
                    path = base / rel
                    if path.is_file():
                        text = path.read_text(encoding="utf-8")
                        break
                self._file_cache[rel] = text
        return self._file_cache[rel]

    def literal(self, rel: str, name: str) -> Optional[object]:
        """Evaluate the module-level assignment ``name = <literal>`` in a
        repo file via the AST — no import, so no jax dependency.  Calls
        to ``frozenset(...)``/``dict(...)``/``tuple(...)`` over literals
        are unwrapped, and references to *earlier* module-level literal
        names resolve (e.g. ``TRACE_KINDS`` reusing ``_LIFECYCLE``).
        Returns None when absent or non-literal."""
        text = self.read(rel)
        if text is None:
            return None
        try:
            tree = ast.parse(text)
        except SyntaxError:
            return None
        env: Dict[str, object] = {}
        for node in tree.body:
            targets: List[ast.expr] = []
            value: Optional[ast.expr] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            for tgt in targets:
                if isinstance(tgt, ast.Name):
                    evaluated = _literal_eval(value, env)
                    if evaluated is not None:
                        env[tgt.id] = evaluated
                    if tgt.id == name:
                        return evaluated
        return None


def _literal_eval(node: ast.expr,
                  env: Optional[Dict[str, object]] = None) -> Optional[object]:
    """``ast.literal_eval`` extended to unwrap ``frozenset(...)`` /
    ``set(...)`` / ``dict(...)`` / ``tuple(...)`` / ``list(...)`` calls
    and resolve names bound earlier in ``env``."""
    env = env or {}
    if isinstance(node, ast.Name):
        return env.get(node.id)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        ctor = node.func.id
        if ctor in ("frozenset", "set", "tuple", "list", "dict"):
            if not node.args and not node.keywords:
                return {"frozenset": frozenset(), "set": set(),
                        "tuple": (), "list": [], "dict": {}}[ctor]
            if len(node.args) == 1 and not node.keywords:
                inner = _literal_eval(node.args[0], env)
                if inner is None:
                    return None
                try:
                    return {"frozenset": frozenset, "set": set,
                            "tuple": tuple, "list": list,
                            "dict": dict}[ctor](inner)
                except TypeError:
                    return None
        return None
    if isinstance(node, ast.Dict):
        out = {}
        for k, v in zip(node.keys, node.values):
            if k is None:
                return None
            key, val = _literal_eval(k, env), _literal_eval(v, env)
            if key is None or val is None:
                return None
            out[key] = val
        return out
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


class Rule:
    """Base class for a project-level lint pass.

    Subclasses set ``name``/``description``/``severity`` and implement
    :meth:`run`, yielding findings via :meth:`finding` (which applies
    the inline-suppression table and reports reasonless suppressions).
    """

    name = "abstract"
    description = ""
    severity = "error"

    def run(self, ctx: RepoContext) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, mod: Module, node, message: str,
                severity: Optional[str] = None) -> Optional[Finding]:
        """Build a Finding for ``node`` (an AST node or an int line
        number) unless an inline suppression covers it."""
        line = node if isinstance(node, int) else getattr(node, "lineno", 1)
        if mod.suppressed(self.name, line):
            return None
        return Finding(rule=self.name, path=mod.rel, line=line,
                       message=message,
                       severity=severity or self.severity)


_REGISTRY: Dict[str, Type[Rule]] = {}


def register(cls: Type[Rule]) -> Type[Rule]:
    """Class decorator adding a Rule to the global registry."""
    if cls.name in _REGISTRY and _REGISTRY[cls.name] is not cls:
        raise ValueError(f"duplicate lint rule name {cls.name!r}")
    _REGISTRY[cls.name] = cls
    return cls


def all_rules() -> Dict[str, Type[Rule]]:
    """Registered rules by name (import ``repro.analysis`` to populate)."""
    return dict(_REGISTRY)


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]
    files: int

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]


def _collect_files(root: pathlib.Path,
                   paths: Sequence[str]) -> List[pathlib.Path]:
    files: List[pathlib.Path] = []
    for p in paths:
        path = (root / p) if not pathlib.Path(p).is_absolute() \
            else pathlib.Path(p)
        files.extend([path] if path.is_file() else sorted(path.rglob("*.py")))
    return files


def _suppression_findings(mod: Module) -> List[Finding]:
    out = []
    for lineno, (rules, reason) in sorted(mod.suppressions.items()):
        if reason is None:
            out.append(Finding(
                rule="suppression", path=mod.rel, line=lineno,
                message="suppression is missing its reason — write "
                        "'# repro-lint: disable=<rule> -- why'"))
        unknown = rules - set(_REGISTRY) - {"*"}
        if unknown:
            out.append(Finding(
                rule="suppression", path=mod.rel, line=lineno,
                message=f"suppression names unknown rule(s) "
                        f"{sorted(unknown)}"))
    return out


def lint_paths(paths: Sequence[str],
               root: Optional[pathlib.Path] = None,
               rules: Optional[Sequence[str]] = None) -> LintResult:
    """Run the registered rules over ``paths`` (files or directories,
    resolved against ``root``, default: the repo checkout).  Returns a
    :class:`LintResult`; the caller decides the exit status from
    ``result.errors``."""
    if root is None:
        root = pathlib.Path(__file__).resolve().parents[3]
    files = _collect_files(root, paths)

    modules: List[Module] = []
    findings: List[Finding] = []
    for f in files:
        try:
            rel = str(f.resolve().relative_to(root.resolve()))
        except ValueError:
            rel = str(f)
        rel = rel.replace("\\", "/")
        try:
            text = f.read_text(encoding="utf-8")
        except OSError as e:
            findings.append(Finding(rule="parse", path=rel, line=1,
                                    message=f"unreadable: {e}"))
            continue
        try:
            tree = ast.parse(text, filename=rel)
        except SyntaxError as e:
            findings.append(Finding(rule="parse", path=rel,
                                    line=e.lineno or 1,
                                    message=f"syntax error: {e.msg}"))
            continue
        modules.append(Module(f, rel, text, tree))

    ctx = RepoContext(modules, repo_root=root)
    for mod in modules:
        findings.extend(_suppression_findings(mod))

    selected = rules if rules is not None else sorted(_REGISTRY)
    for name in selected:
        cls = _REGISTRY.get(name)
        if cls is None:
            raise KeyError(f"unknown lint rule {name!r} "
                           f"(known: {sorted(_REGISTRY)})")
        findings.extend(cls().run(ctx))

    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return LintResult(findings=findings, files=len(modules))
