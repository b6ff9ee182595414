"""Which box of the fast path imports which (README "Component map"),
read with ``ast``: nothing here imports the package."""

import ast
import pathlib

import pytest

PKG = pathlib.Path(__file__).resolve().parent.parent / "txflow_tpu"
BASE = {"utils", "analysis", "codec", "crypto", "native"}
HOST = BASE | {"types"}
# low to high: a box imports only what its entry names, all of it below
ALLOWED = {
    **{box: BASE for box in sorted(BASE)},
    "types": BASE,
    "prep_proc": HOST,
    "ops": HOST | {"prep_proc"},
    "parallel": HOST | {"prep_proc", "ops"},
    "verifier": HOST | {"ops", "parallel"},
    "trace": HOST,
    "store": HOST,
    "abci": HOST,
    "pool": HOST | {"trace"},
}
ALLOWED["engine"] = set(ALLOWED)
# debts, by name: delete the entry with the import and the case tightens
EXCEPTIONS = {("verifier", "engine.hostprep")}


def imports_of(box):
    """Dotted modules of the package, outside ``box``, that it imports."""
    single = PKG / f"{box}.py"
    out = set()
    for path in [single] if single.exists() else sorted((PKG / box).rglob("*.py")):
        here = ("txflow_tpu",) + path.relative_to(PKG).parts[:-1]
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                targets = [tuple(a.name.split(".")) for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mod = here[: len(here) - node.level + 1] if node.level else ()
                mod += tuple(node.module.split(".")) if node.module else ()
                targets = [mod] if len(mod) > 1 else [mod + (a.name,) for a in node.names]
            else:
                continue
            out |= {
                ".".join(t[1:]) for t in targets
                if t[0] == "txflow_tpu" and len(t) > 1 and t[1] != box
            }
    return out


@pytest.mark.parametrize("box", list(ALLOWED))
def test_box_imports_only_what_is_below_it(box):
    found = imports_of(box)
    debts = {mod for b, mod in EXCEPTIONS if b == box}
    assert debts <= found, f"paid: delete {debts - found} from EXCEPTIONS"
    upward = {m for m in found - debts if m.split(".")[0] not in ALLOWED[box]}
    assert not upward, f"{box} imports {sorted(upward)}"
