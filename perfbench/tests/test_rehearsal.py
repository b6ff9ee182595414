"""A CPU rehearsal of each cell at a tiny size: the scalar verifier, a
2-second window. It proves paths, arguments and the shape of the result
line. Its numbers are host numbers of a toy and are written nowhere.

Also the control, kept as a test: the timed path broken underneath (a
verdict altered where it is produced, an answer altered at the commit, a
quorum that never comes) has to read ``correct`` false.
"""

import json
import os
import subprocess
import sys

import pytest

from perfbench.harness import cells, drive
from perfbench.tests.test_cells import tiny_flood

TINY_SERVED = {"rate_tps": 20, "lead_s": 1, "sign_workers": 2, "compare_txs": 32}
CELLS = [w["name"] for w in cells.benchmark()["workloads"]]


def rehearse(name, *, fault=None, trace=False, wait=8.0):
    cell = cells.Cell(name)
    flood = cell.traffic["kind"] == "flood"
    over = tiny_flood(int(cell.config["validators"])) if flood else TINY_SERVED
    opt = drive.Options(
        seed=2**31 + 11, seconds=2, scalar=True, overrides=over, fault=fault,
        commit_wait_s=wait,
    )
    return cell, drive.run_cell(cell, opt)


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_prints_the_contracts_keys(name):
    cell, result = rehearse(name)
    keys = list(result)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"  # each number compared beside its limit, last
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert all(set(c) == {"value", "limit"} for c in result["checks"].values())
    assert result["device"]["platform"] == "cpu"  # stamped: never a device number
    json.dumps(result)


@pytest.mark.parametrize("name", ["val4-flood", "val64-served"])
@pytest.mark.parametrize("fault, number", [
    ("accept_all", "cert_invalid_sig"),
    ("app_corrupt", "app_wrong"),
    ("reject_some", "never_committed"),
])
def test_a_broken_timed_path_reads_not_correct(name, fault, number):
    _, result = rehearse(name, fault=fault, wait=3.0)
    assert result["correct"] is False
    assert result["checks"][number]["value"] > 0, result["checks"]


def test_without_a_chip_a_run_fails_loudly_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "val4-flood", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
