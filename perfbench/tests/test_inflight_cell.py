"""``val64-flood`` (PR 30): the cell's files, a CPU rehearsal at a size
where a chunk is four rungs, so that a quorum takes several steps and the
chunk's last rung arrives after the commit (the rehearsal of
``test_cells.py`` feeds 4 txs to a 256-vote rung: one step a quorum), and
the three readers this PR adds, on hand-made contexts and on the spans the
rehearsed node recorded.
"""

import json
import os

import pytest

from perfbench.harness import cells, drive
from perfbench.tests.test_cells import EMPTY_CTX
from perfbench.tests.test_span_metrics import ctx_of

# a chunk of 4 txs is 256 votes, four drains of the 64-vote top rung: 16
# validators' votes a step, the quorum (43 votes) in the third
FOUR_RUNGS = {
    "backlog_txs": 64, "chunk_txs": 4, "rungs": [16, 64], "rate_hint_tps": 400,
    "lead_s": 2, "lead_txs": 32, "sign_workers": 2, "compare_txs": 48,
    "pools": {"size": 2 * 68 * 64, "cache_size": 4 * 68 * 64},
    "engine": {"max_batch": 64, "min_batch": 16},
}
NEW = ("verified_per_commit", "late_drop_share", "carry_ms")


def test_the_cells_files_say_what_the_issue_asks():
    cell = cells.Cell("val64-flood")
    cfg, traffic = cell.config, cell.traffic
    assert cell.config_name == "val64-inflight" and cell.chips == 1
    assert cfg["validators"] == cfg["votes_per_tx"] == 64 and cfg["hosted_nodes"] == 1
    assert cfg["in_flight_txs"] == traffic["backlog_txs"] == 4096
    assert cfg["in_flight_votes"] == 4096 * 64
    assert cfg["published"]["in_flight_txs"] == 1_000_000
    assert sorted(cfg["reduced"]) == ["hosted_nodes", "in_flight_txs", "in_flight_votes"]
    assert cfg["published"]["in_flight_votes"] == 64 * cfg["published"]["in_flight_txs"]
    # the same net as val64, word for word where they share a key; the source is the same
    # entry of BASELINE.json, named by the clause each file runs: two files, two sources
    val64 = cells.Cell("val64-served").config
    assert cfg["source"] != val64["source"] and "configs[3]" in cfg["source"]
    assert cfg["source"] == next(c for c in cells.benchmark()["configs"]
                                 if c["name"] == "val64-inflight")["source"]
    for key in ("chain_id", "stake_each", "byzantine", "guarantees", "app",
                "consensus_ticker"):
        assert cfg[key] == val64[key], key
    assert cfg["assumed"]["key_seed"] == val64["assumed"]["key_seed"]
    # the traffic file as it is; the cell's own numbers beside their reasons
    assert traffic["kind"] == "flood" and traffic["chunk_txs"] == 256
    assert traffic["engine"] == {"coalesce_linger": 1.0, "idle_flush": 0.0}
    assert traffic["warm"] == [["fused", 4096, 4096]]
    resident = (traffic["backlog_txs"] + traffic["chunk_txs"]) * 64
    assert traffic["pools"] == {"size": 2 * resident, "cache_size": 4 * resident}
    # the lead-in fills the vote dedup set before the window opens, in whole backlogs
    assert traffic["lead_txs"] % traffic["backlog_txs"] == 0
    assert (traffic["lead_txs"] + traffic["backlog_txs"]) * 64 > traffic["pools"]["cache_size"]
    assert traffic["compare_txs"] >= 512 and traffic["rate_hint_tps"] % 50 == 0
    own = json.load(open(os.path.join(cells.BENCH, "cells", "val64-flood.json")))
    for key, why in (("pools", "why_pools"), ("lead_txs", "why_lead"),
                     ("rate_hint_tps", "why_rate_hint"), ("compare_txs", "why_compare")):
        assert key in own and own[why], key
    # a chunk's votes are four drains of the top rung
    assert traffic["chunk_txs"] * 64 == 4 * max(traffic["rungs"])


def test_the_cell_reports_the_floods_layers_and_its_own():
    cell = cells.Cell("val64-flood")
    assert {m["name"] for m in cell.end_to_end} == {"commit_tx_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert all(n.endswith(".val64flood") for n in names)
    flood = {cells.stem(m["name"]): m for m in cells.Cell("val4-flood").per_layer}
    mine = {cells.stem(m["name"]): m for m in cell.per_layer}
    assert set(mine) == set(flood) | {"commit_apply_ms"} | set(NEW)
    for stem, m in flood.items():  # units, sources and layers as the .flood entries
        assert {k: mine[stem][k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: m[k] for k in ("unit", "better", "source", "layer", "moves")
        }, stem
    for m in cell.per_layer:
        assert m["workloads"] == ["val64-flood"] and m["moves"] == "commit_tx_per_s"


@pytest.mark.parametrize("stem", NEW)
def test_new_readers_find_nothing_on_an_empty_context(stem):
    read = cells.metric_reader(stem + ".val64flood")
    assert read(EMPTY_CTX) is None
    assert read(dict(ctx_of({}), commit_times=[])) is None
    # the parent's spans, and a window in which nothing committed
    assert read(dict(ctx_of({"host_prep": [0.08]}, votes=4096), commit_times=[])) is None


def test_verified_per_commit_is_votes_routed_over_commits_in_the_window():
    read = cells.metric_reader("verified_per_commit.val64flood")
    ctx = ctx_of({}, votes=40 * 4096)
    ctx["commit_times"] = [99.0] * 256 + [101.0 + i / 100 for i in range(2560)] + [136.0] * 256
    assert read(ctx) == pytest.approx(64.0)
    ctx["votes"] = 30 * 4096
    assert read(ctx) == pytest.approx(48.0)
    ctx["commit_times"] = [99.0, 136.0]  # none inside the window
    assert read(ctx) is None


def test_late_drop_share_and_carry_ms_read_their_families():
    share = cells.metric_reader("late_drop_share.val64flood")
    carry = cells.metric_reader("carry_ms.val64flood")
    steps = {"carry_prior": [0.0004, 0.0002, 0.0100]}
    # the program records the families and no drain dropped anything: 0, not nothing
    assert share(ctx_of(steps)) == 0.0
    assert share(ctx_of(dict(steps, late_drop=[0.010, 0.025]))) == pytest.approx(0.1)
    assert share(ctx_of({"late_drop": [0.010]})) is None  # never without carry_prior
    assert carry(ctx_of(steps)) == pytest.approx(0.4)  # the median: one stalled step


@pytest.fixture(scope="module")
def rehearsed():
    """One scalar rehearsal at four rungs a chunk; the node's counters and
    the readers' context are taken where ``drive.finish`` gets them."""
    seen = {}
    finish = drive.finish

    def watching(cell, opt, device, sut, corp, **kw):
        seen["pipeline"] = sut.pipeline()
        seen["ctx"] = kw["ctx"]
        seen["values"] = {
            m["name"]: cells.metric_reader(m["name"])(kw["ctx"]) for m in cell.per_layer
        }
        return finish(cell, opt, device, sut, corp, **kw)

    drive.finish = watching
    try:
        opt = drive.Options(seed=2**31 + 30, seconds=2, scalar=True, overrides=FOUR_RUNGS,
                            commit_wait_s=20)
        result = drive.run_cell(cells.Cell("val64-flood"), opt)
    finally:
        drive.finish = finish
    return result, seen


def test_rehearsal_at_four_rungs_a_chunk_is_correct(rehearsed):
    result, _ = rehearsed
    assert result["correct"] is True, result["checks"]
    assert result["workload"] == "val64-flood" and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"commit_tx_per_s", "setup_s"}
    assert result["metrics"]["commit_tx_per_s"]["value"] > 0
    for number in ("cert_invalid_sig", "never_committed", "backlog_over_bound",
                   "backlog_ran_dry", "off_top_rung_dispatches"):
        assert result["checks"][number]["value"] == 0, number
    assert result["diagnostics"]["linger_flushes"] == 0  # only full rungs


def test_rehearsal_carries_quorums_over_steps_and_meets_late_votes(rehearsed):
    result, seen = rehearsed
    pipe = seen["pipeline"]
    assert pipe["carried_slots"] > 0 and 0 < pipe["open_vote_sets"] <= 64
    # a chunk's last rung is late whichever way it goes: dropped in prep
    # or verified and thrown away; validator 1's corrupted votes aside,
    # every vote is in a certificate or late
    assert pipe["late_votes"] + pipe["late_verified"] >= 16 * 4
    assert pipe["dup_votes"] == 0
    values = seen["values"]
    # the window's edges: votes are routed all through it, commits come a
    # chunk (4 steps of 64 votes) at a time
    edge = 4 * 64 / max(result["diagnostics"]["commits_in_window"], 1)
    assert 43.0 <= values["verified_per_commit.val64flood"] <= 64.0 + edge
    assert values["carry_ms.val64flood"] > 0
    assert values["late_drop_share.val64flood"] is not None
    assert values["batch_votes.val64flood"] == pytest.approx(64.0)
    ctx = seen["ctx"]
    carry = ctx["spans"]("carry_prior", ctx["t_open"], ctx["t_close"])
    assert len(carry) >= ctx["pipeline"]["steps"] - 2 > 0  # one a step
