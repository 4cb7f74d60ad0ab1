"""trace-safety: no host↔device syncs inside jitted round-loop code.

The engine's BSP contract (DESIGN.md §3, §6) is that the only host
syncs are the *deliberate* ones at round boundaries (the driver reading
``open_work`` / admission bookkeeping).  Anything that forces a device
readback *inside* traced code — ``.item()``, ``int()/bool()/float()``
of a traced value, ``np.asarray`` of a device array, a Python
``if``/``while`` branching on a traced operand — either breaks tracing
outright or, worse, silently re-traces / re-syncs every round.

The pass works in three stages, all purely static:

1. **Traced-context discovery.**  Any function object passed to a
   tracing primitive (``jax.jit``, ``jax.shard_map``, ``jax.vmap``,
   ``jax.lax.while_loop/fori_loop/cond/scan/switch``,
   ``pl.pallas_call``, ``pl.when`` — call or decorator form, including
   ``partial(jax.jit, ...)``) is traced.  Builders are propagated one
   level: ``jax.jit(make_round(...))`` marks the functions *returned
   by* ``make_round`` as traced (the repo's round/expand/step closures
   are all built this way).  Resolution follows module-level names,
   ``from repro.x import y`` symbols and ``import repro.x as m``
   aliases across every analyzed file.
2. **Closure propagation.**  Functions *called by name* from traced
   bodies are traced transitively (``round_fn`` → ``expand`` → ``step``
   → ``steal.balance_device`` → ...).  Methods and attribute calls that
   do not resolve to an analyzed function are out of scope (v1
   limitation, documented in DESIGN.md §10).  The profiler's
   ``repro.obs.spans.span``/``scope`` are host helpers run at trace
   time by design, and are not propagated into.
3. **Taint + hazard scan** per traced function: positional parameters
   (minus those with static scalar annotations — ``int``, ``bool``,
   ``Optional[int]`` etc. declare compile-time values) and results of
   ``jnp.``/``jax.``/``lax.``/``pl.``-rooted calls are traced values;
   taint flows through assignments, tuple unpacking and ``for``
   targets to a fixpoint.  Hazards are reported where a tainted value
   reaches a sync construct.  ``x.shape``/``.ndim``/``.dtype``/``.size``
   are static metadata and ``is None``/``isinstance`` tests are
   host-side by construction, so neither taints a branch.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.core import Finding, Module, RepoContext, Rule, register

# Attribute-form tracing primitives: X.<name>(fn, ...) marks fn traced.
_PRIMITIVE_ATTRS = {
    "jit", "vmap", "pmap", "shard_map", "pallas_call",
    "while_loop", "fori_loop", "cond", "scan", "switch", "when",
    "checkpoint", "remat", "custom_jvp", "custom_vjp",
}
# Bare-name forms accepted (unambiguous enough to match without a root).
_PRIMITIVE_NAMES = {"jit", "vmap", "shard_map", "pallas_call"}

#: Annotations declaring a parameter static (host-side) by contract.
_STATIC_ANNOTATIONS = {
    "int", "bool", "float", "str", "bytes",
    "Optional[int]", "Optional[bool]", "Optional[float]", "Optional[str]",
    "Sequence[str]", "Tuple[str, ...]", "Tuple[str,...]", "List[str]",
}

#: Attribute reads that are static metadata, not device values.
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

#: Roots whose call results are traced arrays.
_TRACED_ROOTS = {"jnp", "jax", "lax", "pl", "pltpu"}

#: jax.* functions that return *host* values, not traced arrays.
_HOST_API = {
    "default_backend", "devices", "local_devices", "device_count",
    "local_device_count", "process_index", "process_count",
}

_SYNC_METHODS = {"item", "tolist", "block_until_ready"}

#: Host helpers that traced code calls at trace time by design: the
#: profiler's span and scope (``repro.obs.spans``) check a literal name
#: against their tables and return a context manager.  Nothing of theirs
#: is traced, so calls into them do not make them traced functions.
_TRACE_TIME_HOST = {("src/repro/obs/spans.py", "span"),
                    ("src/repro/obs/spans.py", "scope")}


class _FuncInfo:
    __slots__ = ("node", "mod", "parent", "local_funcs",
                 "builder_values", "lambdas", "traced")

    def __init__(self, node, mod: Module, parent: Optional["_FuncInfo"]):
        self.node = node              # FunctionDef | AsyncFunctionDef | Lambda
        self.mod = mod
        self.parent = parent
        self.local_funcs: Dict[str, "_FuncInfo"] = {}
        self.builder_values: Dict[str, ast.expr] = {}   # name = some_call(...)
        self.lambdas: List["_FuncInfo"] = []
        self.traced = False

    @property
    def name(self) -> str:
        return getattr(self.node, "name", "<lambda>")


class _ModuleIndex:
    __slots__ = ("mod", "funcs", "import_modules", "import_symbols",
                 "numpy_aliases")

    def __init__(self, mod: Module):
        self.mod = mod
        self.funcs: Dict[str, _FuncInfo] = {}        # module-level defs
        self.import_modules: Dict[str, str] = {}     # alias -> dotted
        self.import_symbols: Dict[str, Tuple[str, str]] = {}  # alias -> (mod, name)
        self.numpy_aliases: Set[str] = set()


class _Project:
    """Cross-file index: functions, imports, and every call site with
    its enclosing function scope."""

    def __init__(self, ctx: RepoContext):
        self.ctx = ctx
        self.indexes: Dict[str, _ModuleIndex] = {}   # Module.rel -> index
        self.calls: List[Tuple[ast.Call, Optional[_FuncInfo], Module]] = []
        self.all_funcs: List[_FuncInfo] = []
        for mod in ctx.modules:
            self._index_module(mod)

    # -- construction -----------------------------------------------------

    def _index_module(self, mod: Module) -> None:
        idx = _ModuleIndex(mod)
        self.indexes[mod.rel] = idx
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    asname = alias.asname or alias.name.split(".")[0]
                    idx.import_modules[asname] = (
                        alias.name if alias.asname else
                        alias.name.split(".")[0])
                    if alias.name == "numpy":
                        idx.numpy_aliases.add(asname)
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    asname = alias.asname or alias.name
                    full = f"{node.module}.{alias.name}"
                    if node.module == "numpy":
                        idx.numpy_aliases.add(asname)
                    idx.import_modules.setdefault(asname, full)
                    idx.import_symbols[asname] = (node.module, alias.name)
        for stmt in mod.tree.body:
            self._visit(stmt, mod, idx, None)

    def _visit(self, node, mod: Module, idx: _ModuleIndex,
               scope: Optional[_FuncInfo]) -> None:
        """Recursive visitor: collect functions (with their scope
        chain), builder bindings, and every call site."""
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            info = _FuncInfo(node, mod, scope)
            self.all_funcs.append(info)
            if scope is None:
                idx.funcs.setdefault(node.name, info)
            else:
                scope.local_funcs[node.name] = info
            for dec in node.decorator_list:
                self._visit(dec, mod, idx, scope)
                if _is_primitive_expr(dec):
                    info.traced = True
            for default in (node.args.defaults + node.args.kw_defaults):
                if default is not None:
                    self._visit(default, mod, idx, scope)
            for stmt in node.body:
                self._visit(stmt, mod, idx, info)
            return
        if isinstance(node, ast.Lambda):
            info = _FuncInfo(node, mod, scope)
            self.all_funcs.append(info)
            if scope is not None:
                scope.lambdas.append(info)
            self._visit(node.body, mod, idx, info)
            return
        if isinstance(node, ast.ClassDef):
            # Methods resolve like module-scope siblings of the class
            # body; the class adds no name scope for our purposes.
            for dec in node.decorator_list:
                self._visit(dec, mod, idx, scope)
            for stmt in node.body:
                self._visit(stmt, mod, idx, scope)
            return
        if isinstance(node, ast.Assign) and scope is not None and \
                isinstance(node.value, ast.Call):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    scope.builder_values[tgt.id] = node.value
        if isinstance(node, ast.Call):
            self.calls.append((node, scope, mod))
        for child in ast.iter_child_nodes(node):
            self._visit(child, mod, idx, scope)

    # -- resolution -------------------------------------------------------

    def resolve_name(self, name: str, scope: Optional[_FuncInfo],
                     mod: Module) -> Optional[_FuncInfo]:
        s = scope
        while s is not None:
            if name in s.local_funcs:
                return s.local_funcs[name]
            s = s.parent
        idx = self.indexes[mod.rel]
        if name in idx.funcs:
            return idx.funcs[name]
        sym = idx.import_symbols.get(name)
        if sym is not None:
            target = self.ctx.by_dotted.get(sym[0])
            if target is not None:
                tindex = self.indexes.get(target.rel)
                if tindex and sym[1] in tindex.funcs:
                    return tindex.funcs[sym[1]]
        return None

    def resolve_func_expr(self, expr, scope, mod) -> Optional[_FuncInfo]:
        """Resolve a callable expression to an analyzed function."""
        if isinstance(expr, ast.Name):
            return self.resolve_name(expr.id, scope, mod)
        if isinstance(expr, ast.Attribute) and isinstance(expr.value,
                                                          ast.Name):
            idx = self.indexes[mod.rel]
            dotted = idx.import_modules.get(expr.value.id)
            if dotted is not None:
                target = self.ctx.by_dotted.get(dotted)
                if target is not None:
                    tindex = self.indexes.get(target.rel)
                    if tindex and expr.attr in tindex.funcs:
                        return tindex.funcs[expr.attr]
        return None

    def builder_binding(self, name: str,
                        scope: Optional[_FuncInfo]) -> Optional[ast.expr]:
        s = scope
        while s is not None:
            if name in s.builder_values:
                return s.builder_values[name]
            s = s.parent
        return None

    # -- traced marking ---------------------------------------------------

    def returned_functions(self, info: _FuncInfo) -> List[_FuncInfo]:
        out: List[_FuncInfo] = []
        node = info.node
        if isinstance(node, ast.Lambda):
            return out
        for stmt in _walk_own_statements(node):
            if isinstance(stmt, ast.Return) and stmt.value is not None:
                val = stmt.value
                if isinstance(val, ast.Name):
                    target = self.resolve_name(val.id, info, info.mod)
                    if target is not None:
                        out.append(target)
                elif isinstance(val, ast.Lambda):
                    for lam in info.lambdas:
                        if lam.node is val:
                            out.append(lam)
        return out

    def mark_callable_arg(self, arg, scope, mod,
                          worklist: List[_FuncInfo]) -> None:
        """An expression passed where a traced callable is expected."""
        if isinstance(arg, ast.Lambda):
            for info in self.all_funcs:
                if info.node is arg:
                    _mark(info, worklist)
            return
        if isinstance(arg, ast.Call):
            # partial(fn, ...) -> fn;  builder(...) -> builder's returns
            if _callee_name(arg.func) == "partial" and arg.args:
                self.mark_callable_arg(arg.args[0], scope, mod, worklist)
                return
            inner = self.resolve_func_expr(arg.func, scope, mod)
            if inner is not None:
                for ret in self.returned_functions(inner):
                    _mark(ret, worklist)
            return
        target = self.resolve_func_expr(arg, scope, mod)
        if target is None and isinstance(arg, ast.Name):
            bound = self.builder_binding(arg.id, scope)
            if bound is not None and isinstance(bound, ast.Call):
                inner = self.resolve_func_expr(bound.func, scope, mod)
                if inner is not None:
                    for ret in self.returned_functions(inner):
                        _mark(ret, worklist)
            return
        if target is not None:
            _mark(target, worklist)


def _mark(info: _FuncInfo, worklist: List[_FuncInfo]) -> None:
    if not info.traced:
        info.traced = True
        worklist.append(info)


def _callee_name(func_expr) -> Optional[str]:
    if isinstance(func_expr, ast.Name):
        return func_expr.id
    if isinstance(func_expr, ast.Attribute):
        return func_expr.attr
    return None


def _is_primitive_expr(expr) -> bool:
    """True for ``jax.jit`` / ``@partial(jax.jit, ...)`` style exprs."""
    if isinstance(expr, ast.Call):
        if _callee_name(expr.func) == "partial" and expr.args:
            return _is_primitive_expr(expr.args[0])
        return _is_primitive_expr(expr.func)
    if isinstance(expr, ast.Attribute):
        return expr.attr in _PRIMITIVE_ATTRS
    if isinstance(expr, ast.Name):
        return expr.id in _PRIMITIVE_NAMES
    return False


def _walk_own_statements(func_node):
    """Statements of a function body, descending into control flow but
    not into nested function/class definitions."""
    todo = list(func_node.body)
    while todo:
        stmt = todo.pop(0)
        yield stmt
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        for field in ("body", "orelse", "finalbody"):
            todo.extend(getattr(stmt, field, []) or [])
        for handler in getattr(stmt, "handlers", []) or []:
            todo.extend(handler.body)


def _static_annotation(ann) -> bool:
    if ann is None:
        return False
    try:
        return ast.unparse(ann) in _STATIC_ANNOTATIONS
    except Exception:
        return False


class _Taint:
    """Per-function taint engine + hazard reporting."""

    def __init__(self, project: _Project, info: _FuncInfo):
        self.project = project
        self.info = info
        self.mod = info.mod
        self.numpy_aliases = project.indexes[info.mod.rel].numpy_aliases
        self.tainted: Set[str] = set()
        self._seed_params()

    def _seed_params(self) -> None:
        node = self.info.node
        args = node.args
        positional = list(args.posonlyargs) + list(args.args)
        n_defaults = len(args.defaults)
        for a in positional:
            if _static_annotation(a.annotation) or a.arg in ("self", "cls"):
                continue
            self.tainted.add(a.arg)
        # kw-only params are static config by repo convention (tile=,
        # stages=, interpret=...); params with literal defaults that are
        # plain constants are treated as static too.
        for a, default in zip(positional[len(positional) - n_defaults:],
                              args.defaults):
            if isinstance(default, ast.Constant):
                self.tainted.discard(a.arg)

    # -- taint computation -----------------------------------------------

    def is_tainted(self, expr) -> bool:
        if isinstance(expr, ast.Name):
            return expr.id in self.tainted
        if isinstance(expr, ast.Attribute):
            if expr.attr in _STATIC_ATTRS:
                return False
            return self.is_tainted(expr.value)
        if isinstance(expr, ast.Call):
            name = _callee_name(expr.func)
            if name in ("int", "bool", "float", "len", "isinstance",
                        "range", "type", "str"):
                return False     # host-scalar results (flagged elsewhere)
            root = expr.func
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id in _TRACED_ROOTS:
                return name not in _HOST_API
            if isinstance(expr.func, ast.Attribute) and \
                    self.is_tainted(expr.func.value):
                return True      # method on a traced value
            return any(self.is_tainted(a) for a in expr.args) or \
                any(self.is_tainted(kw.value) for kw in expr.keywords)
        if isinstance(expr, ast.Constant):
            return False
        return any(self.is_tainted(child)
                   for child in ast.iter_child_nodes(expr)
                   if isinstance(child, ast.expr))

    def _taint_target(self, tgt) -> bool:
        # Subscript/attribute stores (`buf[i] = x`) do not taint the
        # container name — only whole-name (re)bindings propagate.
        if isinstance(tgt, (ast.Subscript, ast.Attribute)):
            return False
        changed = False
        for node in ast.walk(tgt):
            if isinstance(node, ast.Name) and node.id not in self.tainted:
                self.tainted.add(node.id)
                changed = True
        return changed

    def propagate(self) -> None:
        node = self.info.node
        if isinstance(node, ast.Lambda):
            return
        for _ in range(20):
            changed = False
            for stmt in _walk_own_statements(node):
                if isinstance(stmt, ast.Assign):
                    if self.is_tainted(stmt.value):
                        for tgt in stmt.targets:
                            changed |= self._taint_target(tgt)
                elif isinstance(stmt, ast.AugAssign):
                    if self.is_tainted(stmt.value) and \
                            isinstance(stmt.target, ast.Name):
                        changed |= self._taint_target(stmt.target)
                elif isinstance(stmt, ast.AnnAssign):
                    if stmt.value is not None and \
                            not _static_annotation(stmt.annotation) and \
                            self.is_tainted(stmt.value):
                        changed |= self._taint_target(stmt.target)
                elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                    if self.is_tainted(stmt.iter):
                        changed |= self._taint_target(stmt.target)
            if not changed:
                break

    # -- hazards ----------------------------------------------------------

    def _host_safe_test(self, test) -> bool:
        """Tests that never force a device sync even on traced values."""
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._host_safe_test(test.operand)
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return True
        if isinstance(test, ast.Call) and \
                _callee_name(test.func) == "isinstance":
            return True
        return False

    def hazards(self, rule: Rule) -> List[Finding]:
        node = self.info.node
        out: List[Finding] = []

        def add(anchor, msg):
            f = rule.finding(self.mod, anchor, msg)
            if f is not None:
                out.append(f)

        if isinstance(node, ast.Lambda):
            exprs = [node.body]
        else:
            exprs = []
            for stmt in _walk_own_statements(node):
                if isinstance(stmt, ast.While) and \
                        self.is_tainted(stmt.test) and \
                        not self._host_safe_test(stmt.test):
                    add(stmt, "Python `while` on a traced value inside "
                              "jitted code — restructure with "
                              "jax.lax.while_loop or hoist to the host "
                              "round boundary")
                if isinstance(stmt, ast.If) and \
                        self.is_tainted(stmt.test) and \
                        not self._host_safe_test(stmt.test):
                    add(stmt, "Python `if` on a traced value inside "
                              "jitted code — use jnp.where/lax.cond")
                for child in ast.iter_child_nodes(stmt):
                    if isinstance(child, ast.expr):
                        exprs.append(child)

        seen_calls = set()
        for expr in exprs:
            for call in ast.walk(expr):
                if not isinstance(call, ast.Call) or id(call) in seen_calls:
                    continue
                seen_calls.add(id(call))
                name = _callee_name(call.func)
                if name in ("int", "bool", "float") and call.args and \
                        isinstance(call.func, ast.Name) and \
                        self.is_tainted(call.args[0]):
                    add(call, f"`{name}()` of a traced value forces a "
                              "host sync inside jitted code — keep it a "
                              "jnp scalar or sync at the round boundary")
                elif isinstance(call.func, ast.Attribute) and \
                        call.func.attr in _SYNC_METHODS and \
                        self.is_tainted(call.func.value):
                    add(call, f"`.{call.func.attr}()` on a traced value "
                              "forces a host sync inside jitted code")
                elif isinstance(call.func, ast.Attribute) and \
                        call.func.attr in ("asarray", "array") and \
                        isinstance(call.func.value, ast.Name) and \
                        call.func.value.id in self.numpy_aliases and \
                        any(self.is_tainted(a) for a in call.args):
                    add(call, "`np.asarray`/`np.array` of a device array "
                              "forces a host transfer inside jitted code "
                              "— use jnp equivalents")
                elif isinstance(call.func, ast.Attribute) and \
                        call.func.attr == "device_get" and \
                        any(self.is_tainted(a) for a in call.args):
                    add(call, "`jax.device_get` inside jitted code forces "
                              "a host transfer")
        return out


@register
class TraceSafetyRule(Rule):
    name = "trace-safety"
    description = ("host-sync constructs inside functions reachable from "
                   "jax.jit / shard_map round-loop entry points")
    severity = "error"

    def run(self, ctx: RepoContext) -> List[Finding]:
        project = _Project(ctx)

        # Stage 1: primitive call sites mark their callable arguments.
        worklist: List[_FuncInfo] = [f for f in project.all_funcs
                                     if f.traced]
        for call, scope, mod in project.calls:
            if not _is_primitive_expr(call.func):
                continue
            for arg in list(call.args) + [kw.value for kw in call.keywords]:
                project.mark_callable_arg(arg, scope, mod, worklist)

        # Stage 2: propagate through calls from traced bodies.  Lambdas
        # defined in a traced function trace with it, so calls recorded
        # under lambda scopes flow naturally.
        calls_by_scope: Dict[int, List[ast.Call]] = {}
        for call, scope, _mod in project.calls:
            if scope is not None:
                calls_by_scope.setdefault(id(scope), []).append(call)
        processed: Set[int] = set()
        while worklist:
            info = worklist.pop()
            if id(info) in processed:
                continue
            processed.add(id(info))
            for lam in info.lambdas:
                _mark(lam, worklist)
            for call in calls_by_scope.get(id(info), []):
                target = project.resolve_func_expr(
                    call.func, info, info.mod)
                if target is not None:
                    if (target.mod.rel, target.name) not in \
                            _TRACE_TIME_HOST:
                        _mark(target, worklist)
                    continue
                if isinstance(call.func, ast.Name):
                    bound = project.builder_binding(call.func.id, info)
                    if isinstance(bound, ast.Call):
                        inner = project.resolve_func_expr(
                            bound.func, info, info.mod)
                        if inner is not None:
                            for ret in project.returned_functions(inner):
                                _mark(ret, worklist)
                elif isinstance(call.func, ast.Call):
                    inner = project.resolve_func_expr(
                        call.func.func, info, info.mod)
                    if inner is not None:
                        for ret in project.returned_functions(inner):
                            _mark(ret, worklist)

        # Stage 3: taint + hazard scan over every traced function.
        findings: List[Finding] = []
        seen = set()
        for info in project.all_funcs:
            if not info.traced:
                continue
            taint = _Taint(project, info)
            taint.propagate()
            for f in taint.hazards(self):
                key = (f.path, f.line, f.message)
                if key not in seen:
                    seen.add(key)
                    findings.append(f)

        # Stage 4: the host half of the BSP contract — the per-round
        # service path gets ONE deliberate device sync (the open-work
        # readback).  Reading lane *placement* state (`active`/`inst`)
        # back via np.asarray anywhere reachable from step_round must be
        # event-driven (guarded by a dirty flag), not per-round.
        for mod in ctx.modules:
            findings.extend(self._round_path_syncs(mod, project))
        return findings

    def _round_path_syncs(self, mod: Module,
                          project: _Project) -> List[Finding]:
        out: List[Finding] = []
        numpy_aliases = project.indexes[mod.rel].numpy_aliases
        if not numpy_aliases:
            return out
        for cls in ast.walk(mod.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            methods = {n.name: n for n in cls.body
                       if isinstance(n, ast.FunctionDef)}
            if "step_round" not in methods:
                continue
            # Intra-class reachability from step_round via self.m() calls.
            reach: Set[str] = set()
            todo = ["step_round"]
            while todo:
                name = todo.pop()
                if name in reach or name not in methods:
                    continue
                reach.add(name)
                for n in ast.walk(methods[name]):
                    if isinstance(n, ast.Call) and \
                            isinstance(n.func, ast.Attribute) and \
                            isinstance(n.func.value, ast.Name) and \
                            n.func.value.id == "self":
                        todo.append(n.func.attr)
            for name in sorted(reach):
                for call in ast.walk(methods[name]):
                    if not isinstance(call, ast.Call):
                        continue
                    f = call.func
                    if not (isinstance(f, ast.Attribute) and
                            f.attr in ("asarray", "array") and
                            isinstance(f.value, ast.Name) and
                            f.value.id in numpy_aliases):
                        continue
                    if not call.args:
                        continue
                    if self._reads_placement(call.args[0]):
                        fnd = self.finding(
                            mod, call,
                            "per-round bookkeeping reads lane placement "
                            "state (`active`/`inst`) back from device on "
                            "the step_round path — make it event-driven "
                            "(host-side dirty flag / mirror); the BSP "
                            "contract allows one deliberate sync per "
                            "round (the open-work vector)")
                        if fnd:
                            out.append(fnd)
        return out

    @staticmethod
    def _reads_placement(arg) -> bool:
        for n in ast.walk(arg):
            if isinstance(n, ast.Attribute) and \
                    n.attr in ("active", "inst"):
                val = n.value
                text = ""
                while isinstance(val, ast.Attribute):
                    text = val.attr + "." + text
                    val = val.value
                if isinstance(val, ast.Name):
                    text = val.id + "." + text
                if "lanes" in text:
                    return True
        return False
