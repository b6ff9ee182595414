"""The opening, rehearsed on the CPU: a deployment no file of the benchmark
describes, made of data alone in the test's own directory.

Seven validators with stake 40, 20, 10, 5, 3, 1, 1: the quorum is 54 of 80,
so two votes can decide a tx (40 + 20) and the other six cannot without the
first (40 in all). Validator 1 corrupts every second signature, so for
half the txs the quorum is 40 + 10 + 5 and the certificate has three rows
where the others have two. The peers' votes come at three delays. Served
and flood, through the traffic files that are there, with the scalar
verifier; then the accept-all control, which has to read not correct.
"""

import copy
import json

import pytest

from perfbench.harness import cells, drive, reference
from perfbench.tests.test_cells import tiny_flood
from perfbench.tests.test_rehearsal import TINY_SERVED

STAKE = [40, 20, 10, 5, 3, 1, 1]
DELAYS_MS = [0, 0, 12, 12, 30, 30, 30]  # by validator; the node's own (0) is not used
TRAFFIC = {"served": "served-light", "flood": "flood"}


def skewed_config(path) -> dict:
    config = {
        "name": "skew7", "source": "a test's: no deployment's", "chain_id": "txflow-bench",
        "validators": 7, "stake": STAKE, "app": "kvstore", "consensus_ticker": False,
        "hosted_nodes": 1, "byzantine": {"validator": 1, "corrupt_one_in": 2},
        "reduced": {"hosted_nodes": "7 -> 1"}, "assumed": {"key_seed": "localnet-val"},
        "guarantees": ["as val4's"],
    }
    path.write_text(json.dumps(config))
    return config


def skewed_cell(kind: str, tmp_path) -> cells.Cell:
    """One more configuration and one more cell as entries, the cell reporting
    what the benchmark's cell of the same traffic reports."""
    bench = copy.deepcopy(cells.benchmark())
    skewed_config(tmp_path / "skew7.json")
    bench["configs"].append({"name": "skew7", "file": str(tmp_path / "skew7.json"),
                             "source": "a test's", "reduced": ["hosted_nodes"], "why": "a test's"})
    like = next(w["name"] for w in bench["workloads"] if w["traffic"] == TRAFFIC[kind])
    name = "skew7-" + kind
    bench["workloads"].append({"name": name, "config": "skew7", "traffic": TRAFFIC[kind],
                               "chips": 1, "why": "a test's"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    return cells.Cell(name, bench)


def rehearse(kind: str, tmp_path, fault=None):
    """Run the cell; keep the sampled txs' certificates and the reference's
    own view of the corpus from where ``drive.finish`` gets them."""
    cell = skewed_cell(kind, tmp_path)
    assert cell.traffic["kind"] == kind and cell.config["stake"] == STAKE
    over = (dict(TINY_SERVED, peer_delay_ms=DELAYS_MS) if kind == "served"
            else tiny_flood(7, backlog_txs=128, lead_txs=64))
    seen = {}
    finish = drive.finish

    def keeping(cell, opt, device, sut, corp, **kw):
        ref = reference.Reference(corp)
        seen["quorum"], seen["ctx"] = ref.quorum, kw["ctx"]
        seen["certs"] = [
            [ref.by_address[row[0]] for row in (sut.answer(corp, i)[0] or [])]
            for i in kw["sample"]
        ]
        seen["corrupt"] = [corp.corrupt(1, i) for i in kw["sample"]]
        return finish(cell, opt, device, sut, corp, **kw)

    drive.finish = keeping
    try:
        opt = drive.Options(seed=2**31 + 35, seconds=2, scalar=True, overrides=over, fault=fault,
                            commit_wait_s=8.0, scratch=str(tmp_path / "scratch"))
        result = drive.run_cell(cell, opt)
    finally:
        drive.finish = finish
    return cell, result, seen


@pytest.mark.parametrize("kind", ["served", "flood"])
def test_a_skewed_stake_and_three_peer_delays_run_as_data_and_read_correct(kind, tmp_path):
    cell, result, seen = rehearse(kind, tmp_path)
    assert result["correct"] is True, result["checks"]
    assert result["workload"] == cell.name and result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert seen["quorum"] == 54
    certs = seen["certs"]
    assert certs and all(certs)
    # every certificate's stake at or over the quorum, by the reference's own sum
    for cert in certs:
        assert sum(power for _, _, power in cert) >= 54, cert
        assert len({v for v, _, _ in cert}) == len(cert)
    # the quorum's vote count differs by tx: 40 + 20 where validator 1 signed well,
    # 40 + 10 + 5 or more where it did not; never without validator 0
    rows = {len(cert) for cert in certs}
    assert min(rows) == 2 and max(rows) >= 3, rows
    assert all(any(v == 0 for v, _, _ in cert) for cert in certs)
    for cert, corrupt in zip(certs, seen["corrupt"]):
        assert (1 in {v for v, _, _ in cert}) != corrupt or len(cert) > 2
    # the counters a reader can reach, whole, as the window opened and as it closed
    counters = seen["ctx"]["counters"]
    assert set(counters) == {"open", "close"}
    for at in counters.values():
        assert {"steps", "prep_s", "coalesce"} <= set(at["pipeline"])
        assert {"votes", "cpu_s", "fast", "general", "primed"} <= set(at["ingest"])
    ingested = counters["close"]["ingest"]["votes"] - counters["open"]["ingest"]["votes"]
    assert ingested > 0
    read = cells.metric_reader("ingest_us_per_vote.x")
    assert read(seen["ctx"]) > 0  # a host number of a toy: written nowhere
    if kind == "served":
        # own 40 + validator 1's 20, delivered with the tx: the path starts at delay 0
        assert seen["ctx"]["quorum_delay_ms"] == 0.0
        # the node's own vote and three frames a tx, one for each delay
        assert ingested >= 7 * (result["attempted"] - 2)
        assert result["diagnostics"]["injector_error"] is None


@pytest.mark.parametrize("kind", ["served", "flood"])
def test_the_accept_all_control_reads_not_correct_on_the_skewed_stake(kind, tmp_path):
    _, result, seen = rehearse(kind, tmp_path, fault="accept_all")
    assert result["correct"] is False
    assert result["checks"]["cert_invalid_sig"]["value"] > 0, result["checks"]
    # what the control lets through: validator 1's corrupted vote counted as 20 of a quorum
    assert any(corrupt and 1 in {v for v, _, _ in cert}
               for cert, corrupt in zip(seen["certs"], seen["corrupt"]))
