"""hub180 (PR 36) on the CPU, small: the served kind through
``drive.run_cell`` on a deployment made by ``hub180.json``'s own generator
(``perfbench/harness/regions.py``: the stake fit, the region draw, the delay
list), cut to 15 validators, the regions' sizes in proportion and the
delays ten times the file's (1.45 s at the most: inside a 2 s window).

The quorum completes in the third frame and mid-frame; the last two frames
arrive after the commit. The run is judged by ``perfbench/harness/reference.py``
and, beside it, each sampled certificate against the scalar golden model
(``TxVoteSet.add_vote``, signatures checked) fed the same votes in the
order they arrive. Then the real ``hub180.json``: its numbers, and that it
holds what its generator makes.
"""

import conftest  # noqa: F401

import collections
import copy
import json
import os
import time

import pytest

from perfbench.harness import cells, corpus, drive, peers, reference, regions
from perfbench.tests.test_rehearsal import TINY_SERVED
from txflow_tpu.types import TxVote
from txflow_tpu.types.vote_set import TxVoteSet

HUB = "perfbench/configs/hub180.json"
CELL = "hub180-wan-served"
N_SMALL = 15
DELAY_SCALE = 10


def _load(path: str) -> dict:
    with open(os.path.join(cells.ROOT, path)) as f:
        return json.load(f)


def scaled_sizes(sizes: list[int], n: int) -> list[int]:
    """The regions' sizes for n validators, in proportion (largest remainder)."""
    exact = [s * n / sum(sizes) for s in sizes]
    out = [int(x) for x in exact]
    by_rest = sorted(range(len(sizes)), key=lambda k: exact[k] - out[k], reverse=True)
    for k in by_rest[: n - sum(out)]:
        out[k] += 1
    return out


def small_deployment():
    """(generator block, what it makes) of hub180 cut to N_SMALL validators:
    the file's exponent, total, thresholds and delays times DELAY_SCALE."""
    gen = _load(HUB)["generator"]
    sizes = scaled_sizes([r[1] for r in gen["regions"]], N_SMALL)
    small = dict(gen, validators=N_SMALL, regions=[
        [name, size, delay * DELAY_SCALE] for (name, _, delay), size in zip(gen["regions"], sizes)
    ])
    return small, regions.build(small)


def small_cell(tmp_path, made) -> cells.Cell:
    """The small deployment as one more configuration and cell, reporting
    what ``hub180-wan-served`` reports."""
    config = dict(_load(HUB), name="hub15", validators=N_SMALL, stake=made["stake"])
    path = tmp_path / "hub15.json"
    path.write_text(json.dumps(config))
    bench = copy.deepcopy(cells.benchmark())
    bench["configs"].append({"name": "hub15", "file": str(path), "source": "a test's",
                             "reduced": ["hosted_nodes"], "why": "a test's"})
    bench["workloads"].append({"name": "hub15-served", "config": "hub15",
                               "traffic": "served-light", "chips": 1, "why": "a test's"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("hub15-served")
    return cells.Cell("hub15-served", bench)


class _NoProfiler(drive.Tracing):
    """--trace 1 without the profiler, which finds no device on the CPU:
    every tx sampled, the per-layer readers run, no device trace."""

    def arm(self, t_open, t_close):
        pass

    def stop(self):
        pass

    def reduce(self):
        return None


def golden_certificate(corp, delays, i, own_row, val_set):
    """The scalar golden model's certificate of tx i: the node's own vote,
    then the peers' frames by rising delay, each in validator order, added
    with their signatures checked until the stake first passes 2/3."""
    key = corp.tx_key(i)
    hx = key.hex().upper()
    vs = TxVoteSet(corp.chain_id, 0, hx, key, val_set)
    addr, sig, ts, height, tx_hash = own_row
    arriving = [TxVote(height, tx_hash, key, ts, addr, sig)]
    for _, group in peers.frames(delays, corp.signer_idx):
        for k, v in group:
            arriving.append(TxVote(0, hx, key, corpus.vote_timestamp(i, corp.n_vals, v),
                                   corpus.address(corp.pub_keys[v]), corp.sig(k, i)))
    for vote in arriving:
        added, err = vs.add_vote(vote)
        assert added and err is None
        if vs.has_two_thirds_majority():
            break
    return sorted((c.validator_address, c.signature, c.timestamp_ns, c.height, c.tx_hash)
                  for c in vs.make_commit().commits)


def run_small(tmp_path, monkeypatch, device: bool):
    gen, made = small_deployment()
    cell = small_cell(tmp_path, made)
    over = dict(TINY_SERVED, peer_delay_ms=made["peer_delay_ms"], compare_txs=64)
    if device:
        # the device verifier on the CPU backend at the small rung: one tx a
        # second, so that a step (about 0.25 s of CPU at 16 votes) ends
        # before the next frame is due and nothing reaches the 64 rung
        over.update(rate_tps=1, lead_s=2, rungs=[16, 64], warm=[["fused", 16, 16]])
        monkeypatch.setattr(drive, "device_info", lambda scalar, chips: {
            "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(drive, "Tracing", _NoProfiler)
    seen = {}
    finish = drive.finish

    def keeping(cell, opt, device, sut, corp, **kw):
        # the late frames of the last txs come up to 1.45 s after the window:
        # every delivered vote drained before the counters are read
        deadline = time.monotonic() + 30
        while sut.node.tx_vote_pool.size() and time.monotonic() < deadline:
            time.sleep(0.05)
        time.sleep(0.2)
        seen.update(
            pipeline=sut.pipeline(), ingest=sut.node.tx_vote_pool.ingest_stats(),
            pool=sut.node.tx_vote_pool.size(), spans=sut.node.tracer.spans(),
            sample=kw["sample"], ctx=kw["ctx"], corp=corp, val_set=sut.val_set,
            answers={i: sut.answer(corp, i) for i in range(corp.n_txs)},
            own_addr=sut.priv_vals[0].get_address(),
        )
        return finish(cell, opt, device, sut, corp, **kw)

    monkeypatch.setattr(drive, "finish", keeping)
    opt = drive.Options(seed=2**31 + 36, seconds=2, scalar=not device, trace=True,
                        overrides=over, commit_wait_s=20.0, scratch=str(tmp_path / "scratch"))
    result = drive.run_cell(cell, opt)
    return gen, made, cell, result, seen


@pytest.mark.parametrize("device", [False, True], ids=["scalar", "device-cpu"])
def test_a_three_frame_quorum_with_late_frames_reads_correct(device, tmp_path, monkeypatch):
    gen, made, cell, result, seen = run_small(tmp_path, monkeypatch, device)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(c["value"] == 0 for c in result["checks"].values())
    ctx, corp, stats = seen["ctx"], seen["corp"], seen["pipeline"]
    # the quorum completes in the third frame: NA-West's delay, scaled
    assert ctx["quorum_delay_ms"] == gen["regions"][2][2] > 0
    assert seen["pool"] == 0

    # every certificate the arrival-order prefix whose stake first passes
    # 2/3, row for row the golden model's; no vote of a late frame in one
    late_region = {v for region in made["regions"][3:] for v in region}
    late_addr = {corpus.address(corp.pub_keys[v]) for v in late_region}
    quorum = reference.Reference(corp).quorum
    committed = [i for i, (rows, _, _) in seen["answers"].items() if rows]
    assert len(committed) == corp.n_txs
    for i in seen["sample"]:
        rows = seen["answers"][i][0]
        own = [r for r in rows if r[0] == seen["own_addr"]]
        assert len(own) == 1
        golden = golden_certificate(corp, made["peer_delay_ms"], i, own[0], seen["val_set"])
        assert sorted(rows) == golden
        assert not {r[0] for r in rows} & late_addr
        stake = {corpus.address(pk): p for pk, p in zip(corp.pub_keys, corp.powers)}
        assert sum(stake[r[0]] for r in rows) >= quorum

    # the counters: late frames dropped in prep, the rest of the deciding
    # frame verified and found late, every delivered vote accounted for
    assert stats["late_votes"] > 0 and stats["late_verified"] > 0
    assert stats["quorums"] == corp.n_txs
    delivered = seen["ingest"]["votes"]
    assert delivered == corp.n_txs * N_SMALL
    assert delivered == (stats["quorum_rows"] + stats["late_votes"] + stats["late_verified"]
                         + stats["dup_votes"])
    assert stats["quorum_rows"] / stats["quorums"] < N_SMALL
    assert stats["quorum_steps"] / stats["quorums"] >= 2

    # one quorum_wait span a committed tx, every tx sampled
    waits = collections.Counter(s["tx"] for s in seen["spans"] if s["name"] == "quorum_wait")
    hashes = {corp.tx_key(i).hex().upper() for i in committed}
    assert set(waits) == hashes and set(waits.values()) == {1}

    # the readers this PR adds read the window
    metrics = result["metrics"]
    assert 0 < metrics["cert_rows.hub180"]["value"] < N_SMALL
    assert metrics["quorum_steps.hub180"]["value"] >= 2
    assert metrics["quorum_wait_ms.hub180"]["value"] > 0
    assert metrics["late_drop_share.hub180"]["value"] > 0
    assert metrics["verified_per_commit.hub180"]["value"] < N_SMALL
    # the deciding frame ends its hold as it lands: the coalescer's
    # quorum flushes, one a committed tx at most
    assert stats["coalesce"]["quorum_flushes"] > 0
    assert 0 < metrics["quorum_flush_share.hub180"]["value"] <= 100


def test_hub180_json_is_what_its_generator_makes():
    config = _load(HUB)
    cell = cells.Cell(CELL)
    gen = config["generator"]
    stake = corpus.powers_of(config)
    assert len(stake) == config["validators"] == 180
    assert sum(stake) == config["stake_total"] < 2**30  # the device tally is int32
    # the fit: the least exponent on the grid at which 7 hold more than 1/3
    assert regions.fit_exponent(180, gen["total_stake"], gen["nakamoto"], 1 / 3,
                                gen["exponent_step"]) == gen["exponent"]
    assert regions.top_share(stake, 7) > 1 / 3 >= regions.top_share(stake, 6)
    made = regions.build(gen)
    assert made["stake"] == stake and made["seed"] == gen["seed"]
    assert [r["validators"] for r in config["regions"]] == made["regions"]
    assert cell.traffic["peer_delay_ms"] == made["peer_delay_ms"]
    delays = peers.delays_of(cell.traffic, 180)
    assert len(set(delays)) == 5 and len(peers.frames(delays, list(range(1, 180)))) == 5
    # the quorum in the third frame, with 5% of the stake of room on both sides
    c = regions.cumulative(stake, made["regions"])
    assert c[1] <= 0.617 and c[2] >= 0.717
    assert peers.quorum_delay_ms(stake, delays, own=0) == config["quorum_delay_ms"] == 75.0
    assert config["hosted_validator"]["index"] == 0 and 0 in made["regions"][0]
    assert "byzantine" not in config
    assert config["guarantees"] == _load("perfbench/configs/val64.json")["guarantees"]
    # every region's share as the file states it
    shares = [b - a for a, b in zip([0.0] + c, c)]
    assert [r["stake_share"] for r in config["regions"]] == [round(s, 6) for s in shares]
