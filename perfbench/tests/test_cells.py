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
    # at this size the scalar verifier would take the whole backlog in one step
    "engine": {"max_batch": 64, "min_batch": 16},
}


def tiny_flood(n_vals: int, **over) -> dict:
    """``TINY_FLOOD`` for a cell of ``n_vals`` validators, its pools by
    ``perfbench/cells/val64-flood.json``'s own rule: twice and four times
    the votes of the backlog plus one chunk."""
    sizes = dict(TINY_FLOOD, **over)
    resident = (sizes["backlog_txs"] + sizes["chunk_txs"]) * n_vals
    sizes["pools"] = {"size": 2 * resident, "cache_size": 4 * resident}
    return sizes


def test_benchmark_json_names_what_the_issue_names():
    """Whatever a PR's issue names: the lists hold together. A configuration
    is some cell's, a cell's configuration and traffic are files, a pair of
    them is one cell, and the arithmetic of an end-to-end quantity (the part
    of its name before the dot) is one kind's."""
    bench = cells.benchmark()
    configs = [c["name"] for c in bench["configs"]]
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(configs)) == len(configs) and len(set(names)) == len(names)
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["file"].startswith("perfbench/configs/")
        assert os.path.isfile(os.path.join(cells.ROOT, c["file"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]  # every cell reports it
    for m in bench["end_to_end"]:
        assert set(m.get("workloads", names)) <= set(names), m["name"]
        # a suffixed metric is one quantity held apart for its spread: same unit, same sense
        base = e2e.get(cells.stem(m["name"]), m)
        assert all(m[k] == base[k] for k in ("unit", "better", "source")), m["name"]
    for name in names:
        cell = cells.Cell(name)
        stems = [cells.stem(m["name"]) for m in cell.end_to_end]
        assert len(set(stems)) == len(stems), name  # one reading of a quantity in a cell


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
    kind = cells.kind(cell.traffic["kind"])
    assert callable(kind.run)
    # the kind runs what the file states
    drive.check_runs(cell.traffic["kind"], cell.config, kind.RUNS)
    if "arrivals" in cell.traffic:
        assert callable(cells.arrivals(cell.traffic["arrivals"]))
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


_NO_COUNTS = {"pipeline": {}, "ingest": {"votes": 0, "cpu_s": 0.0}}
EMPTY_CTX = {
    "client": None, "trace": None, "votes": 0, "pipeline": {"steps": 0, "prep_s": 0, "route_s": 0},
    "counters": {"open": _NO_COUNTS, "close": _NO_COUNTS}, "quorum_delay_ms": 0.0,
    "commit_times": [], "t_open": 0.0, "t_close": 1.0, "spans": lambda name, t0, t1: [],
    "rung_votes": 64, "rung_slots": 64, "device_kind": "TPU v5 lite",
}


SERVED = [w["name"] for w in cells.benchmark()["workloads"]
          if cells.Cell(w["name"]).traffic["kind"] == "served"]


def test_served_cells_carry_their_own_rate():
    assert SERVED
    for name in SERVED:
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
    over = tiny_flood(64, rungs=[64, 256], backlog_txs=32, chunk_txs=4, rate_hint_tps=300)
    opt = drive.Options(seed=3, seconds=1.5, scalar=True, overrides=over, commit_wait_s=20)
    result = drive.run_cell(cell, opt)
    assert result["workload"] == "val64-flood"
    assert set(result["metrics"]) == {"commit_tx_per_s", "setup_s"}
    assert result["checks"]["cert_invalid_sig"]["value"] == 0
    assert result["checks"]["never_committed"]["value"] == 0
