#!/usr/bin/env python
"""txlint CLI — project-invariant static analysis for txflow-tpu.

Usage:
    python tools/lint.py              # human-readable report, exit 0
    python tools/lint.py --check     # exit 1 on any unsuppressed violation
    python tools/lint.py --json      # machine-readable report
    python tools/lint.py --suppressed  # also list suppressed violations
    python tools/lint.py --update-pins # re-record twin-path fingerprints
    python tools/lint.py --prune-suppressions  # delete stale allow() comments
    python tools/lint.py --race-report # pretty-print .race_audit.json

Exit codes: 0 clean, 1 violations under --check (or races under
--race-report), 2 scan errors.

Rules, suppression syntax, and the runtime auditors are documented in
README.md "Static analysis & concurrency hygiene".
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from txflow_tpu.analysis import core  # noqa: E402
from txflow_tpu.analysis import twins  # noqa: E402

RACE_REPORT = REPO_ROOT / ".race_audit.json"

# strip the allow() comment (and any trailing space before it) from a line
_PRUNE_RE = re.compile(r"\s*#\s*txlint:\s*allow\([^)]*\)(?:\s*--\s*.*)?$")


def _prune_suppressions(report: dict) -> int:
    """Rewrite files deleting every allow() comment flagged stale."""
    stale = [v for v in report["violations"] if v.rule == "stale-suppression"]
    by_file: dict[str, list[int]] = {}
    for v in stale:
        by_file.setdefault(v.path, []).append(v.line)
    pruned = 0
    for rel, lines in sorted(by_file.items()):
        path = REPO_ROOT / rel
        text = path.read_text().splitlines(keepends=True)
        for ln in lines:
            src = text[ln - 1]
            newline = "\n" if src.endswith("\n") else ""
            stripped = _PRUNE_RE.sub("", src.rstrip("\n"))
            text[ln - 1] = (stripped + newline) if stripped.strip() else newline
            pruned += 1
            print(f"pruned {rel}:{ln}")
        path.write_text("".join(text))
    return pruned


def _race_report() -> int:
    """Pretty-print the race-audit dump the tier-1 conftest gate writes."""
    if not RACE_REPORT.exists():
        print(
            f"no {RACE_REPORT.name} — run the suite with TXFLOW_RACE_AUDIT=1 "
            "(tier-1 default) to produce it"
        )
        return 0
    report = json.loads(RACE_REPORT.read_text())
    fields = report.get("fields", {})
    races = report.get("races", [])
    print(f"race audit: {len(fields)} declared field name(s), {len(races)} race(s)")
    for name, s in sorted(fields.items()):
        lockset = s.get("lockset")
        guard = (
            "handoff-only" if lockset is None and s.get("handoffs")
            else "single-thread" if lockset is None
            else "{" + ", ".join(lockset) + "}" if lockset
            else "EMPTY"
        )
        print(
            f"  {name}: {s.get('fields', 0)} instance(s), "
            f"{s.get('reads', 0)}r/{s.get('writes', 0)}w, "
            f"max {s.get('max_threads', 0)} thread(s), "
            f"{s.get('handoffs', 0)} handoff(s), lockset {guard}"
            + ("  [RACY]" if s.get("racy") else "")
        )
    for r in races:
        print(
            f"  RACE {r['field']}: unlocked {r['access']} at {r['site']} "
            f"(thread {r['thread']}) races {r['other_site']} "
            f"(thread {r['other_thread']})"
        )
        if r.get("stack"):
            print(f"    at: {r['stack']}")
    return 1 if races else 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="txlint", description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero on any unsuppressed violation")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the full report as JSON")
    ap.add_argument("--suppressed", action="store_true",
                    help="also print suppressed violations")
    ap.add_argument("--update-pins", action="store_true",
                    help="re-record twin-path fingerprints in twins.json")
    ap.add_argument("--prune-suppressions", action="store_true",
                    help="rewrite files deleting stale allow() comments")
    ap.add_argument("--race-report", action="store_true",
                    help="pretty-print the runtime race-audit dump "
                         "(.race_audit.json) and exit 1 on races")
    args = ap.parse_args(argv)

    if args.update_pins:
        pins = twins.update_pins(REPO_ROOT)
        print(f"re-pinned {len(pins['twins'])} twin group(s) -> {twins.PIN_FILE}")
        return 0

    if args.race_report:
        return _race_report()

    report = core.lint_tree(REPO_ROOT)

    if args.prune_suppressions:
        n = _prune_suppressions(report)
        print(f"txlint: pruned {n} stale suppression(s)")
        return 0

    if args.as_json:
        json.dump(core.report_to_json(report), sys.stdout, indent=2)
        print()
    else:
        for v in report["violations"]:
            print(v.format())
        if args.suppressed:
            for v in report["suppressed"]:
                print(f"{v.format()} -- {v.justification}")
        for e in report["errors"]:
            print(f"ERROR: {e}", file=sys.stderr)
        n, s = len(report["violations"]), len(report["suppressed"])
        print(
            f"txlint: {report['files_scanned']} files, "
            f"{n} violation(s), {s} suppressed"
        )
    if report["errors"]:
        return 2
    if args.check and report["violations"]:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
