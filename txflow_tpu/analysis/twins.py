"""twin-path: pin hand-synced duplicate logic to its parity tests.

The mempool deliberately keeps an inlined, non-raising batch twin of its
scalar ingest path (``check_tx_many`` vs ``_check_tx_locked``). The twins
MUST evolve together, and the only mechanical guard is the parity tests
that replay both paths against each other. (The vote pool needs none:
its ``check_tx`` is a one-vote frame through ``check_tx_many``.)

This pass pins each twin function's AST fingerprint together with its
registered parity test file's content hash in ``twins.json`` (committed).
If a twin function changes while every registered parity test file is
byte-identical to the pinned state, the lint fails: whoever edited the
twin must extend/touch the parity tests, then re-record with
``tools/lint.py --update-pins``. Any other drift from the pinned state
(parity file changed, function renamed/moved) also fails, with a message
pointing at ``--update-pins`` — the pin file is an acknowledgment log,
so it must be rewritten in the same change.

Fingerprints are ``ast.dump`` hashes (no line numbers), so moving a twin
within its file or editing unrelated code never trips the rule.
"""

from __future__ import annotations

import ast
import hashlib
import json
from pathlib import Path

from .core import LintPass, Violation

PIN_FILE = Path(__file__).with_name("twins.json")


def _func_fingerprint(repo_root: Path, spec: str) -> str | None:
    """spec = "rel/path.py::ClassName.func" or "rel/path.py::func"."""
    rel, _, qual = spec.partition("::")
    path = repo_root / rel
    if not path.exists():
        return None
    tree = ast.parse(path.read_text(), filename=rel)
    parts = qual.split(".")
    node: ast.AST = tree
    for p in parts:
        found = None
        for child in getattr(node, "body", []):
            if (
                isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))
                and child.name == p
            ):
                found = child
                break
        if found is None:
            return None
        node = found
    return hashlib.sha256(ast.dump(node).encode()).hexdigest()


def _file_fingerprint(repo_root: Path, rel: str) -> str | None:
    path = repo_root / rel
    if not path.exists():
        return None
    return hashlib.sha256(path.read_bytes()).hexdigest()


def load_pins(pin_file: Path = PIN_FILE) -> dict:
    if not pin_file.exists():
        return {"twins": {}}
    return json.loads(pin_file.read_text())


def update_pins(repo_root: Path, pin_file: Path = PIN_FILE) -> dict:
    """Recompute every fingerprint in the pin file from the current tree
    and rewrite it (the acknowledgment step after a twin+test change)."""
    pins = load_pins(pin_file)
    for twin in pins["twins"].values():
        for spec in twin["functions"]:
            twin["functions"][spec] = _func_fingerprint(repo_root, spec)
        for rel in twin["parity_tests"]:
            twin["parity_tests"][rel] = _file_fingerprint(repo_root, rel)
    pin_file.write_text(json.dumps(pins, indent=2, sort_keys=True) + "\n")
    return pins


class TwinPathPass(LintPass):
    name = "twin-path"

    def __init__(self, pin_file: Path = PIN_FILE):
        self.pin_file = pin_file

    def run(self, module):  # file-level pass: everything happens in finalize
        return []

    def finalize(self, repo_root: Path) -> list[Violation]:
        pins = load_pins(self.pin_file)
        out: list[Violation] = []
        pin_rel = self.pin_file.name
        for twin_name, twin in pins.get("twins", {}).items():
            changed_funcs: list[str] = []
            missing: list[str] = []
            for spec, pinned in twin["functions"].items():
                now = _func_fingerprint(repo_root, spec)
                if now is None:
                    missing.append(spec)
                elif now != pinned:
                    changed_funcs.append(spec)
            tests_changed = False
            for rel, pinned in twin["parity_tests"].items():
                now = _file_fingerprint(repo_root, rel)
                if now is None:
                    missing.append(rel)
                elif now != pinned:
                    tests_changed = True
            if missing:
                out.append(
                    Violation(
                        "twin-path", pin_rel, 1,
                        f"twin '{twin_name}': pinned target(s) not found: "
                        f"{missing} — fix the spec in analysis/twins.json and "
                        "run tools/lint.py --update-pins",
                    )
                )
                continue
            if changed_funcs and not tests_changed:
                out.append(
                    Violation(
                        "twin-path", pin_rel, 1,
                        f"twin '{twin_name}' changed ({changed_funcs}) but its "
                        f"parity tests {list(twin['parity_tests'])} are "
                        "byte-identical to the pinned state — hand-synced twins "
                        "must be re-proven: update the parity tests, then run "
                        "tools/lint.py --update-pins",
                    )
                )
            elif changed_funcs or tests_changed:
                out.append(
                    Violation(
                        "twin-path", pin_rel, 1,
                        f"twin '{twin_name}' pins are stale (functions changed: "
                        f"{changed_funcs or 'no'}, parity tests changed: "
                        f"{tests_changed}) — run tools/lint.py --update-pins to "
                        "acknowledge the paired change",
                    )
                )
        return out
