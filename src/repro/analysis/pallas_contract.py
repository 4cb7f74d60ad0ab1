"""pallas-contract: DESIGN.md §5.2 kernel-contract conformance.

Four statically-checkable clauses for any module that issues a
``pl.pallas_call``:

1. **Pad before divide.**  A grid computed as ``rows // tile`` is only
   exact when the operand was padded to a tile multiple first; the repo
   idiom is ``table = _pad_rows(table, tile)`` before ``shape // tile``.
   A floor-divide by a tile parameter in a pallas-calling function with
   no ``_pad_rows``/``cdiv`` in sight truncates the tail tile silently
   (wrong results on non-multiple shapes — exactly the bug class that
   only fails on TPU).
2. **index_map purity.**  ``BlockSpec`` index maps run at trace time on
   every grid step; they must be pure index arithmetic.  Any function
   call inside an index-map lambda (closures over scalar-prefetch refs
   may subscript, e.g. ``inst_ref[l]``, but never call) is flagged.
3. **VMEM budget.**  Call sites that hard-code ``tile=`` with the
   split-phase layout (``stages=2``) are checked against the v5e's
   VMEM working-set budget (the ``repro.peaks`` estimate, 4 MiB) at the
   documented bound shape (n ≤ 1024 ⇒ w = 32 words, 128 lanes) — the
   same formula ``(tile·w + 2·w·lanes + 4·tile·lanes) · 4 ≤ budget``
   that ``kernels/autotune.predict_cost`` applies at runtime
   (``predict_cost`` is used directly when jax is importable, so the
   lint job needs no accelerator deps otherwise).  ``tile=None`` call
   sites defer to the autotuner and are always fine.
4. **Oracle + parity test.**  Every public kernel entry point in
   ``src/repro/kernels/`` must have a ``<name>_ref`` oracle in
   ``kernels/ref.py`` and be exercised by name somewhere under
   ``tests/`` (the parity suites) — the §5.2 rule that no compiled
   path exists without an interpretable reference.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from repro.analysis.core import Finding, Module, RepoContext, Rule, register

#: Kernel entry points whose ``tile=``/``stages=`` kwargs feed the
#: split-phase layout (autotune.choose candidates).
_TILED_ENTRY_POINTS = {
    "count_stats", "stacked_count_stats", "degree_stats", "degree_argmax",
    "domination_stats", "popcount_reduce", "masked_row_reduce",
}

#: Documented bound shape for the static VMEM check (DESIGN §5.2): the
#: benchmark envelope is n ≤ 1024 variables (w = 32 int32 words) on a
#: 128-lane pool.  Larger deployments must autotune (tile=None).
_N_BOUND = 1024
_LANES_BOUND = 128
_DEFAULT_BUDGET = 4 * 1024 * 1024

_PAD_HELPERS = {"_pad_rows", "pad_rows", "cdiv"}

#: Kernel modules exempt from the oracle clause: the oracle registry
#: itself and the dispatch layer.
_ORACLE_EXEMPT = {"ref.py", "ops.py", "autotune.py"}


def _is_pallas_call(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "pallas_call") or \
        (isinstance(f, ast.Name) and f.id == "pallas_call")


def _callee_name(func_expr) -> Optional[str]:
    if isinstance(func_expr, ast.Name):
        return func_expr.id
    if isinstance(func_expr, ast.Attribute):
        return func_expr.attr
    return None


def _words(n: int) -> int:
    return (n + 31) // 32


def _static_working_set(tile: int, w: int, lanes: int) -> int:
    # Mirrors autotune.predict_cost's stages=2 working-set model: one
    # table tile + both lane masks + the live [tile, lanes] values.
    return (tile * w + 2 * w * lanes + 4 * tile * lanes) * 4


def _predict_over_budget(tile: int, budget: int) -> bool:
    """True when ``tile`` at the bound shape exceeds the VMEM budget.
    Prefers the live ``autotune.predict_cost`` (exact model); falls
    back to the mirrored formula when jax is not importable."""
    w = _words(_N_BOUND)
    try:
        from repro.kernels.autotune import predict_cost
    except Exception:
        return _static_working_set(tile, w, _LANES_BOUND) > budget
    # The interpret model borrows the v5e entry's budget
    # (autotune.INTERPRET_MODEL_KIND) and needs no device to ask.
    cost = predict_cost(_N_BOUND, w, _LANES_BOUND, 1,
                        tile=tile, stages=2, platform="cpu")
    return cost is None


@register
class PallasContractRule(Rule):
    name = "pallas-contract"
    description = ("Pallas kernels obey the §5.2 block/VMEM contract "
                   "and carry ref.py oracles + parity tests")
    severity = "error"

    def run(self, ctx: RepoContext) -> List[Finding]:
        findings: List[Finding] = []
        budget = _DEFAULT_BUDGET

        for mod in ctx.modules:
            has_pallas = any(_is_pallas_call(n) for n in ast.walk(mod.tree)
                             if isinstance(n, ast.Call))
            self._check_tile_call_sites(mod, budget, findings)
            if not has_pallas:
                continue
            self._check_pad_before_divide(mod, findings)
            self._check_index_map_purity(mod, findings)
            self._check_oracles(ctx, mod, findings)
        return findings

    # -- clause 1: pad before divide -------------------------------------

    def _check_pad_before_divide(self, mod: Module,
                                 findings: List[Finding]) -> None:
        for func in ast.walk(mod.tree):
            if not isinstance(func, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if not any(_is_pallas_call(n) for n in ast.walk(func)
                       if isinstance(n, ast.Call)):
                continue
            params = {a.arg for a in (func.args.posonlyargs +
                                      func.args.args +
                                      func.args.kwonlyargs)}
            pads = any(isinstance(n, ast.Call) and
                       _callee_name(n.func) in _PAD_HELPERS
                       for n in ast.walk(func))
            if pads:
                continue
            for n in ast.walk(func):
                if isinstance(n, ast.BinOp) and \
                        isinstance(n.op, ast.FloorDiv) and \
                        isinstance(n.right, ast.Name) and \
                        n.right.id in params and \
                        "tile" in n.right.id:
                    f = self.finding(
                        mod, n,
                        f"grid divides by `{n.right.id}` without padding "
                        "the operand first — call `_pad_rows(x, "
                        f"{n.right.id})` (or use pl.cdiv) so partial "
                        "tiles are not silently dropped (§5.2)")
                    if f:
                        findings.append(f)

    # -- clause 2: index_map purity --------------------------------------

    def _check_index_map_purity(self, mod: Module,
                                findings: List[Finding]) -> None:
        for call in ast.walk(mod.tree):
            if not isinstance(call, ast.Call):
                continue
            name = _callee_name(call.func)
            if name not in ("BlockSpec", "PrefetchScalarGridSpec"):
                continue
            lambdas = [a for a in call.args if isinstance(a, ast.Lambda)]
            lambdas += [kw.value for kw in call.keywords
                        if isinstance(kw.value, ast.Lambda)]
            for lam in lambdas:
                for n in ast.walk(lam.body):
                    if isinstance(n, (ast.Call, ast.NamedExpr, ast.Await,
                                      ast.ListComp, ast.SetComp,
                                      ast.DictComp, ast.GeneratorExp)):
                        f = self.finding(
                            mod, lam,
                            "BlockSpec index_map must be pure index "
                            "arithmetic (names, subscripts, +-*//%); "
                            "it re-runs on every grid step at trace "
                            "time, so calls are forbidden (§5.2)")
                        if f:
                            findings.append(f)
                        break

    # -- clause 3: VMEM budget at hard-coded tile sites ------------------

    def _check_tile_call_sites(self, mod: Module, budget: int,
                               findings: List[Finding]) -> None:
        for call in ast.walk(mod.tree):
            if not isinstance(call, ast.Call):
                continue
            if _callee_name(call.func) not in _TILED_ENTRY_POINTS:
                continue
            kwargs = {kw.arg: kw.value for kw in call.keywords
                      if kw.arg is not None}
            tile = kwargs.get("tile")
            stages = kwargs.get("stages")
            if not (isinstance(tile, ast.Constant) and
                    isinstance(tile.value, int)):
                continue        # tile=None / dynamic -> autotuner decides
            if not (isinstance(stages, ast.Constant) and
                    stages.value == 2):
                continue        # budget model is for the split layout
            if _predict_over_budget(tile.value, budget):
                f = self.finding(
                    mod, call,
                    f"hard-coded tile={tile.value} with stages=2 "
                    f"exceeds the {budget // (1024 * 1024)} MiB VMEM "
                    f"working-set budget at the bound shape "
                    f"(n={_N_BOUND}, lanes={_LANES_BOUND}) — pass "
                    "tile=None to autotune, or shrink the tile (§5.2)")
                if f:
                    findings.append(f)

    # -- clause 4: oracle + parity test ----------------------------------

    def _check_oracles(self, ctx: RepoContext, mod: Module,
                       findings: List[Finding]) -> None:
        if "src/repro/kernels/" not in f"/{mod.rel}" and \
                not mod.rel.startswith("src/repro/kernels/"):
            return
        base = mod.rel.rsplit("/", 1)[-1]
        if base in _ORACLE_EXEMPT:
            return
        ref_text = ctx.read("src/repro/kernels/ref.py") or ""
        tests_text = self._tests_corpus(ctx)
        for node in mod.tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            if node.name.startswith("_"):
                continue
            if f"def {node.name}_ref" not in ref_text:
                f = self.finding(
                    mod, node,
                    f"public kernel `{node.name}` has no "
                    f"`{node.name}_ref` oracle in kernels/ref.py — "
                    "every compiled path needs an interpretable "
                    "reference (§5.2)")
                if f:
                    findings.append(f)
            elif tests_text and node.name not in tests_text:
                f = self.finding(
                    mod, node,
                    f"public kernel `{node.name}` is never exercised "
                    "by name under tests/ — add it to the parity "
                    "suite (§5.2)")
                if f:
                    findings.append(f)

    _tests_cache: Optional[str] = None

    def _tests_corpus(self, ctx: RepoContext) -> str:
        if PallasContractRule._tests_cache is None:
            chunks: List[str] = []
            for base in (ctx.repo_root, ctx.package_root):
                tests = base / "tests"
                if tests.is_dir():
                    for f in sorted(tests.glob("test_*.py")):
                        try:
                            chunks.append(f.read_text(encoding="utf-8"))
                        except OSError:
                            pass
                    break
            PallasContractRule._tests_cache = "\n".join(chunks)
        return PallasContractRule._tests_cache
