"""Results-matrix banking for the scenario grid, under a clean-supersede
contract.

The banked artifact (``bench_artifacts/scenario_grid_latest.json``) is
the regression reference: "handles every scenario" as a matrix of tile
verdicts a later run can be diffed against. Clean means the RUN was
sound — the walk completed without a harness error and every tile got a
real judgment. A clean run ALWAYS overwrites (red tiles are data, not
dirt: a regression must be allowed to update the reference it will be
blamed against); a dirty run (harness crash, infra-breach tiles) never
displaces a clean banked matrix — an artifact that mostly measured a
broken environment is worse than a stale clean one.

``verdict_fingerprint`` is the seed-reproducibility handle: a sha256
over the ordered (tile id, pass, breach) triples, so "same seed, same
verdicts" is one string compare instead of a tree diff.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
ARTIFACT_DIR = os.path.join(REPO_ROOT, "bench_artifacts")
GRID_LATEST = os.path.join(ARTIFACT_DIR, "scenario_grid_latest.json")


def verdict_fingerprint(verdicts: list[dict]) -> str:
    """sha256 over the ordered (tile, pass, breach) triples — the
    matrix's identity for same-seed reproducibility checks."""
    digest = hashlib.sha256()
    for v in verdicts:
        digest.update(
            json.dumps(
                [v.get("tile"), bool(v.get("pass")), v.get("breach")]
            ).encode()
        )
    return digest.hexdigest()


def matrix_clean(matrix: dict) -> bool:
    """A matrix is clean when the walk itself was sound: no harness
    error and no tile judged ``infra`` (a tile failing a REAL gate —
    loss/divergence/slo/adversary/liveness — is clean data)."""
    if matrix.get("error"):
        return False
    tiles = matrix.get("tiles") or []
    if not tiles:
        return False
    return all(t.get("breach") != "infra" for t in tiles)


def build_matrix(grid, tiles_kind: str, verdicts: list[dict],
                 error: str | None = None) -> dict:
    """Assemble the artifact payload from a walk's verdicts."""
    return {
        "kind": "scenario_grid",
        "tiles_kind": tiles_kind,  # "smoke-diagonal" | "full" | "filtered"
        "seed": grid.seed,
        "n_validators": grid.n_validators,
        "axes": {a: list(ls) for a, ls in grid.axes.items()},
        "tiles": verdicts,
        "passed": sum(1 for v in verdicts if v.get("pass")),
        "failed": sum(1 for v in verdicts if not v.get("pass")),
        "verdict_fingerprint": verdict_fingerprint(verdicts),
        "error": error,
    }


def bank_matrix(matrix: dict, path: str = GRID_LATEST) -> bool:
    """Bank under the clean-supersede contract; returns True when the
    artifact was written (False: dirty run held back by a clean bank)."""
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        matrix = dict(
            matrix,
            measured_at_unix=round(time.time(), 1),
            clean=matrix_clean(matrix),
        )
        existing = load_banked(path)
        if (
            existing is not None
            and not matrix["clean"]
            and existing.get("clean", matrix_clean(existing))
        ):
            return False
        with open(path, "w") as f:
            f.write(json.dumps(matrix, indent=1))
        return True
    except OSError:
        return False


def load_banked(path: str = GRID_LATEST) -> dict | None:
    try:
        with open(path) as f:
            return json.loads(f.read())
    except (OSError, ValueError):
        return None
