"""repro-lint pass tests (ISSUE 10): every rule pack fires exactly on
its bad fixture, stays silent on the good one, suppressions behave,
and the full-repo run is clean."""
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.analysis import all_rules, lint_paths  # noqa: E402

FIXTURES = "tests/fixtures/lint"


def _lint(relpath, **kw):
    return lint_paths([relpath], root=ROOT, **kw)


def _rules_hit(result):
    return {f.rule for f in result.findings}


# ---------------------------------------------------------------------------
# rule packs fire on bad fixtures, stay silent on good ones
# ---------------------------------------------------------------------------

PACKS = [
    ("trace-safety", "trace_safety_bad.py", "trace_safety_good.py"),
    ("pallas-contract", "pallas_bad.py", "pallas_good.py"),
    ("telemetry-schema", "telemetry_bad.py", "telemetry_good.py"),
    ("api-hygiene", "api_hygiene_bad.py", "api_hygiene_good.py"),
]


@pytest.mark.parametrize("rule,bad,good", PACKS,
                         ids=[p[0] for p in PACKS])
def test_pack_fires_on_bad_and_only_there(rule, bad, good):
    bad_result = _lint(f"{FIXTURES}/{bad}")
    assert _rules_hit(bad_result) == {rule}, bad_result.findings
    good_result = _lint(f"{FIXTURES}/{good}")
    assert good_result.findings == [], \
        [f.format() for f in good_result.findings]


def test_trace_safety_finds_every_hazard_class():
    result = _lint(f"{FIXTURES}/trace_safety_bad.py")
    messages = " | ".join(f.message for f in result.findings)
    assert "`if` on a traced value" in messages
    assert "`while` on a traced value" in messages
    assert "`int()` of a traced value" in messages
    assert "`.item()` on a traced value" in messages
    assert "np.asarray" in messages
    assert "`bool()` of a traced value" in messages      # builder closure
    assert "per-round bookkeeping" in messages           # step_round path
    assert len(result.findings) >= 7


def test_pallas_contract_finds_every_clause():
    result = _lint(f"{FIXTURES}/pallas_bad.py")
    messages = " | ".join(f.message for f in result.findings)
    assert "without padding" in messages
    assert "index_map must be pure" in messages
    assert "VMEM" in messages
    assert len(result.findings) == 3


def test_telemetry_schema_finds_every_shape():
    result = _lint(f"{FIXTURES}/telemetry_bad.py")
    messages = " | ".join(f.message for f in result.findings)
    assert "unknown progress-event kind 'warp'" in messages
    assert "unknown trace record kind 'bogus'" in messages
    assert "missing required field(s) ['best']" in messages
    assert "unknown lifecycle kind 'nope'" in messages
    assert "unknown progress-event kind 'finished'" in messages
    assert len(result.findings) == 5


def test_telemetry_schema_checks_span_and_scope_names():
    bad = _lint(f"{FIXTURES}/spans_bad.py")
    assert _rules_hit(bad) == {"telemetry-schema"}, bad.findings
    messages = " | ".join(f.message for f in bad.findings)
    assert "unknown span name 'repro.solve.warp'" in messages
    assert "unknown span name 'solve.round'" in messages
    assert "unknown scope name 'repro.solve.round'" in messages
    assert "unknown scope name 'engine.expand'" in messages
    assert "unknown scope name 'steal.global'" in messages
    assert len(bad.findings) == 5
    good = _lint(f"{FIXTURES}/spans_good.py")
    assert good.findings == [], [f.format() for f in good.findings]


def test_api_hygiene_deprecation_clauses():
    result = _lint(f"{FIXTURES}/api_hygiene_bad.py")
    messages = " | ".join(f.message for f in result.findings)
    assert "stacklevel=2" in messages
    assert "should say 'deprecated'" in messages
    assert len(result.findings) == 2


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_suppression_with_reason_silences_reasonless_does_not():
    result = _lint(f"{FIXTURES}/suppressed.py")
    # int(x) is suppressed with a reason; x.item()'s suppression lacks
    # one, which silences the hazard but is itself an error.
    assert _rules_hit(result) == {"suppression"}
    assert len(result.findings) == 1
    assert "missing its reason" in result.findings[0].message


def test_unknown_rule_suppression_reported(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text("x = 1  # repro-lint: disable=no-such-rule -- because\n")
    result = lint_paths([str(src)], root=tmp_path)
    assert any("unknown rule" in f.message for f in result.findings)


# ---------------------------------------------------------------------------
# api-surface snapshot clause (needs a module inside MODULES)
# ---------------------------------------------------------------------------

def _fake_repo(tmp_path, snapshot_text):
    pkg = tmp_path / "src" / "repro" / "obs"
    pkg.mkdir(parents=True)
    (tmp_path / "src" / "repro" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text(
        '__all__ = ["Ghost"]\nGhost = 1\n')
    tools = tmp_path / "tools"
    tools.mkdir()
    (tools / "api_surface.py").write_text('MODULES = ("repro.obs",)\n')
    (tools / "api_surface.txt").write_text(snapshot_text)
    return tmp_path


def test_export_missing_from_snapshot_is_flagged(tmp_path):
    root = _fake_repo(tmp_path, "module repro.obs\n  const Real = 1\n")
    result = lint_paths(["src"], root=root, rules=["api-hygiene"])
    assert any("Ghost" in f.message and "missing from" in f.message
               for f in result.findings), result.findings


def test_module_without_snapshot_section_is_flagged(tmp_path):
    root = _fake_repo(tmp_path, "module repro.other\n")
    result = lint_paths(["src"], root=root, rules=["api-hygiene"])
    assert any("no section" in f.message for f in result.findings)


def test_snapshot_clause_clean_when_synced(tmp_path):
    root = _fake_repo(tmp_path, "module repro.obs\n  const Ghost = 1\n")
    result = lint_paths(["src"], root=root, rules=["api-hygiene"])
    assert result.findings == []


# ---------------------------------------------------------------------------
# whole-repo + CLI
# ---------------------------------------------------------------------------

def test_full_repo_is_clean():
    result = lint_paths(["src"], root=ROOT)
    assert result.errors == [], [f.format() for f in result.errors]
    # Nothing is skipped: every module under src/ is analyzed.
    assert result.files == len(list((ROOT / "src").rglob("*.py")))


def test_registry_has_all_four_packs():
    names = set(all_rules())
    assert {"trace-safety", "pallas-contract", "telemetry-schema",
            "api-hygiene"} <= names


def test_cli_exit_codes_and_json(tmp_path):
    out = tmp_path / "findings.json"
    bad = subprocess.run(
        [sys.executable, "tools/lint.py",
         f"{FIXTURES}/api_hygiene_bad.py", "--json", str(out)],
        cwd=ROOT, capture_output=True, text=True)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    payload = json.loads(out.read_text())
    assert payload["errors"] == 2
    assert all(f["rule"] == "api-hygiene" for f in payload["findings"])

    good = subprocess.run(
        [sys.executable, "tools/lint.py",
         f"{FIXTURES}/api_hygiene_good.py"],
        cwd=ROOT, capture_output=True, text=True)
    assert good.returncode == 0, good.stdout + good.stderr


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "tools/lint.py", "--list-rules"],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 0
    for rule in ("trace-safety", "pallas-contract", "telemetry-schema",
                 "api-hygiene"):
        assert rule in proc.stdout
