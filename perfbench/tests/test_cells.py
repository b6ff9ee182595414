"""Cells are data: every cell's files are found by its names, and a cell
that a later PR adds as data runs with no file edited."""

import copy
import json
import os

import pytest

from perfbench.harness import cells, drive

TINY_FLOOD = {
    "backlog_txs": 256, "chunk_txs": 16, "rungs": [16, 64], "rate_hint_tps": 3000,
    "lead_s": 2, "sign_workers": 2, "compare_txs": 48, "lead_txs": 96,
    "pools": {"size": 5000, "cache_size": 20000},
    # at this size the scalar verifier would take the whole backlog in one step
    "engine": {"max_batch": 64, "min_batch": 16},
}


def test_benchmark_json_names_what_the_issue_names():
    bench = cells.benchmark()
    assert [c["name"] for c in bench["configs"]] == ["val4", "val64"]
    assert [w["name"] for w in bench["workloads"]] == ["val4-flood", "val64-served", "val4-served"]
    assert all(w["chips"] == 1 for w in bench["workloads"])
    # the served latencies once for each configuration: their spreads differ tenfold
    assert {m["name"] for m in bench["end_to_end"]} == {
        "commit_tx_per_s", "commit_p50_ms", "commit_p95_ms", "commit_p50_ms.val64",
        "commit_p95_ms.val64", "setup_s",
    }
    assert {cells.stem(m["name"]) for m in bench["end_to_end"]} == {
        "commit_tx_per_s", "commit_p50_ms", "commit_p95_ms", "setup_s"
    }


def test_one_reader_serves_a_quantity_in_every_cell():
    assert cells.stem("step_ms.val64") == cells.stem("step_ms.flood") == "step_ms"
    assert cells.stem("setup_s") == "setup_s"
    stems = {cells.stem(m["name"]) for m in cells.benchmark()["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(cells.BENCH, "metrics")) if f.endswith(".py")}
    assert stems == files


def test_val4_is_honest_and_only_its_accept_all_control_plants_a_corrupting_peer():
    from perfbench.harness import corpus

    val4, val64 = cells.Cell("val4-flood").config, cells.Cell("val64-served").config
    assert "byzantine" not in val4
    assert corpus.byzantine_of(val4, None) is None
    assert corpus.byzantine_of(val4, "reject_some") is None
    assert corpus.byzantine_of(val4, "accept_all") == {"validator": 1, "corrupt_one_in": 16}
    assert corpus.byzantine_of(val64, None) == corpus.byzantine_of(val64, "accept_all") == {
        "validator": 1, "corrupt_one_in": 4,
    }


@pytest.mark.parametrize("name", [w["name"] for w in cells.benchmark()["workloads"]])
def test_every_cells_files_are_found_by_name(name):
    bench = cells.benchmark()
    cell = cells.Cell(name)
    assert cell.traffic["kind"] in drive.KINDS
    assert cell.config["name"] == cell.config_name
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert sorted(cell.config["reduced"]) == sorted(entry["reduced"])
    assert cell.config["guarantees"] and cell.config["assumed"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e  # the cell reports what the layer metric moves
        assert callable(cells.metric_reader(m["name"]))
        assert cells.metric_reader(m["name"])(EMPTY_CTX) is None  # nothing to read: nothing


EMPTY_CTX = {
    "client": None, "trace": None, "votes": 0, "pipeline": {"steps": 0, "prep_s": 0, "route_s": 0},
    "commit_times": [], "t_open": 0.0, "t_close": 1.0, "spans": lambda name, t0, t1: [],
    "rung_votes": 64, "rung_slots": 64, "device_kind": "TPU v5 lite",
}


def test_served_cells_carry_their_own_rate():
    for name in ("val64-served", "val4-served"):
        own = json.load(open(os.path.join(cells.BENCH, "cells", name + ".json")))
        assert cells.Cell(name).traffic["rate_tps"] == own["rate_tps"] > 0


def test_a_cell_added_as_data_runs_without_editing_a_file():
    """Open question 1 of PERF.md, ``val64-flood``: one more entry of
    ``workloads``, the files that are there."""
    bench = copy.deepcopy(cells.benchmark())
    bench["workloads"].append({
        "name": "val64-flood", "config": "val64", "traffic": "flood", "chips": 1,
        "why": "a test's",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "val4-flood" in m.get("workloads", []):
            m["workloads"].append("val64-flood")
    cell = cells.Cell("val64-flood", bench)
    assert cell.config["validators"] == 64 and cell.traffic["kind"] == "flood"
    over = dict(TINY_FLOOD, rungs=[64, 256], backlog_txs=32, chunk_txs=4, rate_hint_tps=300)
    opt = drive.Options(seed=3, seconds=1.5, scalar=True, overrides=over, commit_wait_s=20)
    result = drive.run_cell(cell, opt)
    assert result["workload"] == "val64-flood"
    assert set(result["metrics"]) == {"commit_tx_per_s", "setup_s"}
    assert result["checks"]["cert_invalid_sig"]["value"] == 0
    assert result["checks"]["never_committed"]["value"] == 0
