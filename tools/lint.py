"""repro-lint CLI — run the repo's static analysis pass (DESIGN.md §10).

  python tools/lint.py                 # lint src (the default surface)
  python tools/lint.py src tools       # explicit paths (files or dirs)
  python tools/lint.py --json out.json # machine-readable findings (CI)
  python tools/lint.py --list-rules    # rule catalogue
  python tools/lint.py --rule trace-safety src   # one rule only

Exit status: 0 when no error-severity findings, 1 otherwise.  The pass
is stdlib-only (no jax import), so this runs anywhere — including the
dependency-free CI ``lint`` job.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import List

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.analysis import all_rules, lint_paths  # noqa: E402


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=None,
                    help="files or directories to lint "
                         "(default: src, resolved against the repo root)")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="also write findings as JSON (use '-' for "
                         "stdout); consumed by the CI artifact upload")
    ap.add_argument("--rule", action="append", default=None,
                    help="run only this rule (repeatable)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalogue and exit")
    args = ap.parse_args(argv)

    rules = all_rules()
    if args.list_rules:
        for name in sorted(rules):
            cls = rules[name]
            print(f"{name:18s} [{cls.severity}] {cls.description}")
        return 0

    for r in (args.rule or []):
        if r not in rules:
            print(f"lint: unknown rule {r!r} (known: {sorted(rules)})",
                  file=sys.stderr)
            return 2

    result = lint_paths(args.paths or ["src"], root=ROOT, rules=args.rule)

    for f in result.findings:
        print(f.format())
    errors = result.errors
    warnings = [f for f in result.findings if f.severity != "error"]
    print(f"lint: {result.files} files, {len(errors)} error(s), "
          f"{len(warnings)} warning(s)")

    if args.json:
        payload = {
            "files": result.files,
            "errors": len(errors),
            "warnings": len(warnings),
            "findings": [
                {"rule": f.rule, "path": f.path, "line": f.line,
                 "severity": f.severity, "message": f.message}
                for f in result.findings
            ],
        }
        text = json.dumps(payload, indent=2)
        if args.json == "-":
            print(text)
        else:
            pathlib.Path(args.json).write_text(text + "\n",
                                               encoding="utf-8")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
