"""The 64-validator flood with its in-flight state (PR 30), against a plain
model of the deployment's semantics.

The traffic of ``val64-flood`` at a small size: a chunk of txs, then one
frame from each of the 64 validators in validator order, a top rung a
quarter of a chunk's votes, so that a tx's quorum is spread over three
engine steps and the chunk's last quarter arrives after the commit.
Validator 1 corrupts one signature in four.

The model is a dict of tx -> votes by validator, fed the same frames in
the same order: signatures checked with ``crypto/ed25519.py``, a tx
committed when the stake of its valid votes first exceeds 2/3. The engine
(a full node on the scalar verifier) has to agree with it at
``pipeline_depth`` 1 and 2: the committed set, every certificate, the stake
a vote set reports at commit, and a vote pool that ends empty. The new
``pipeline_stats()`` counters have to account for every vote delivered,
and the ``late_drop`` / ``carry_prior`` stage spans sit under their step.
"""

import conftest  # noqa: F401

import hashlib
import time

import pytest

from txflow_tpu.crypto import ed25519
from txflow_tpu.node import LocalNet
from txflow_tpu.pool.mempool import TxInfo
from txflow_tpu.types import TxVote
from txflow_tpu.types.priv_validator import MockPV
from txflow_tpu.types.validator import Validator, ValidatorSet
from txflow_tpu.utils.config import test_config as make_test_config
from txflow_tpu.verifier import ScalarVoteVerifier

CHAIN = "inflight-flood"
N_VALS, STAKE = 64, 10
RUNG = 64  # the top rung: 16 validators' votes on a chunk
CHUNK_TXS = 4  # CHUNK_TXS x 64 votes = four rungs
CHUNKS = 6
BYZANTINE, CORRUPT_ONE_IN = 1, 4
QUORUM = 2 * N_VALS * STAKE // 3 + 1  # more than 2/3 of 640: 427


def _corrupt(i: int) -> bool:
    return hashlib.sha256(b"corrupt-%d" % i).digest()[0] % CORRUPT_ONE_IN == 0


def _traffic():
    """(priv_vals, txs, frames): frames in the order they are delivered,
    each (validator, [votes on the chunk's txs])."""
    pvs = [MockPV(hashlib.sha256(b"inflight-val%d" % v).digest()) for v in range(N_VALS)]
    txs = [b"inflight-k%04d=v%04d" % (i, i) for i in range(CHUNKS * CHUNK_TXS)]
    frames = []
    for c in range(CHUNKS):
        for v, pv in enumerate(pvs):
            votes = []
            for i in range(c * CHUNK_TXS, (c + 1) * CHUNK_TXS):
                key = hashlib.sha256(txs[i]).digest()
                vote = TxVote(0, key.hex().upper(), key, 1_700_000_000_000_000_000 + i * N_VALS + v,
                              pv.get_address())
                pv.sign_tx_vote(CHAIN, vote)
                if v == BYZANTINE and _corrupt(i):
                    sig = vote.signature
                    vote.signature = sig[:7] + bytes([sig[7] ^ 0xFF]) + sig[8:]
                votes.append(vote)
            frames.append((v, votes))
    return pvs, txs, frames


class Model:
    """The deployment's semantics, plainly."""

    def __init__(self, pvs):
        self.pub = {pv.get_address(): pv.get_pub_key() for pv in pvs}
        self.votes: dict[str, dict[bytes, TxVote]] = {}  # tx -> valid votes by validator
        self.certificate: dict[str, dict[bytes, TxVote]] = {}  # as the quorum latched
        self.invalid = 0
        self.delivered = 0

    def deliver(self, vote: TxVote) -> None:
        self.delivered += 1
        if not ed25519.verify(self.pub[vote.validator_address], vote.sign_bytes(CHAIN),
                              vote.signature):
            self.invalid += 1
            return
        held = self.votes.setdefault(vote.tx_hash, {})
        held.setdefault(vote.validator_address, vote)
        if vote.tx_hash not in self.certificate and STAKE * len(held) >= QUORUM:
            self.certificate[vote.tx_hash] = dict(held)


def _run(depth: int):
    pvs, txs, frames = _traffic()
    model = Model(pvs)
    for _v, votes in frames:
        for vote in votes:
            model.deliver(vote)

    cfg = make_test_config()
    cfg.trace.sample_rate = 1
    eng = cfg.engine
    eng.pipeline_depth = depth
    eng.max_batch, eng.min_batch = RUNG, 16
    eng.coalesce_linger, eng.idle_flush = 5.0, 0.0  # only full rungs dispatch
    cfg.mempool.size = 2 * len(frames) * CHUNK_TXS
    cfg.mempool.cache_size = 4 * len(frames) * CHUNK_TXS
    val_set = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), STAKE) for pv in pvs])
    verifier = ScalarVoteVerifier(val_set)
    verifier.buckets = (16, RUNG)
    net = LocalNet(N_VALS, chain_id=CHAIN, priv_vals=pvs, voting_power=STAKE, config=cfg,
                   use_device_verifier=False, verifier=verifier, sign=False,
                   mempool_broadcast=False, n_nodes=1, index_txs=False)
    node = net.nodes[0]
    stake_at_commit: dict[str, int] = {}
    enqueue = node.txflow._enqueue_commit

    def recording(vs, step=0):
        stake_at_commit[vs.tx_hash] = vs.stake()
        enqueue(vs, step)

    node.txflow._enqueue_commit = recording
    # the whole flood is in the pools before the engine starts: every
    # drain finds a full rung, so the order of drains and routes is the
    # loop's own and the counters below are exact
    for c in range(CHUNKS):
        chunk = txs[c * CHUNK_TXS:(c + 1) * CHUNK_TXS]
        assert node.mempool.check_tx_many(chunk) == [None] * CHUNK_TXS
        for v, votes in frames[c * N_VALS:(c + 1) * N_VALS]:
            assert node.tx_vote_pool.check_tx_many(votes, TxInfo(1 + v)) == [None] * CHUNK_TXS
    net.start()
    try:
        assert net.wait_all_committed(txs, timeout=60.0)
        deadline = time.monotonic() + 20.0
        while node.tx_vote_pool.size() or not node.txflow.commits_drained():
            assert time.monotonic() < deadline, node.tx_vote_pool.size()
            time.sleep(0.01)
        stats = node.txflow.pipeline_stats()
        invalid = int(node.metrics.invalid_votes.value())
    finally:
        net.stop()
    return {
        "model": model, "node": node, "txs": txs, "stats": stats, "invalid": invalid,
        "stake_at_commit": stake_at_commit, "spans": node.tracer.spans(),
    }


@pytest.fixture(scope="module", params=[1, 2], ids=["depth1", "depth2"])
def run(request):
    return request.param, _run(request.param)


def test_engine_agrees_with_the_model(run):
    _depth, r = run
    model, node = r["model"], r["node"]
    hashes = [hashlib.sha256(tx).hexdigest().upper() for tx in r["txs"]]
    assert set(model.certificate) == set(hashes)  # the traffic commits every tx
    for tx, h in zip(r["txs"], hashes):
        commit = node.tx_store.load_tx_commit(h)
        assert commit is not None and commit.commits, h
        rows = {cs.validator_address: cs for cs in commit.commits}
        assert len(rows) == len(commit.commits)  # distinct validators
        valid = model.votes[h]
        for addr, cs in rows.items():
            assert cs.tx_hash == h
            # a subset of the model's valid votes: none corrupted, each for this tx
            assert addr in valid and cs.signature == valid[addr].signature
        assert STAKE * len(rows) >= QUORUM
        # routed in ingest order: the very votes that were in as the quorum latched
        assert set(rows) == set(model.certificate[h])
        assert r["stake_at_commit"][h] == STAKE * len(rows) == STAKE * len(model.certificate[h])
        assert node.tx_store.load_tx_bytes(h) == tx
        key, _, value = tx.partition(b"=")
        assert node.app.state.get(key) == value
    # every late vote is gone from the pool, and no vote set is left open
    assert node.tx_vote_pool.size() == 0
    assert not node.txflow.vote_sets


def test_the_in_flight_counters_account_for_every_vote(run):
    depth, r = run
    model, stats = r["model"], r["stats"]
    in_certificates = sum(len(c) for c in model.certificate.values())
    assert r["invalid"] == model.invalid > 0
    assert (stats["late_votes"] + stats["late_verified"] + stats["dup_votes"]
            + in_certificates + r["invalid"]) == model.delivered == CHUNKS * CHUNK_TXS * N_VALS
    assert stats["dup_votes"] == 0  # every validator votes once
    # the chunk's last rung (validators 48..63) arrives after the commit:
    # the serial loop preps it after the third step is routed and drops it
    # there; with two steps in flight it is prepped while the third is on
    # the verifier, verified, and thrown away in routing
    last_rung = CHUNKS * RUNG
    same_step = model.delivered - in_certificates - r["invalid"] - last_rung
    if depth == 1:
        assert (stats["late_votes"], stats["late_verified"]) == (last_rung, same_step)
    else:
        assert (stats["late_votes"], stats["late_verified"]) == (0, last_rung + same_step)
    # two rungs of a chunk find its vote sets open: the second and third in
    # the serial loop; the third and fourth with two steps in flight, where
    # a step is prepped before the step before it is routed (its prior
    # stake is one step stale, which is why the host decides every quorum)
    assert stats["carried_slots"] == CHUNKS * 2 * CHUNK_TXS
    assert stats["open_vote_sets"] == CHUNK_TXS


def test_late_drop_and_carry_prior_are_children_of_the_steps_host_prep(run):
    depth, r = run
    spans, stats = r["spans"], r["stats"]
    prep = {}
    for s in spans:
        if s["name"] == "host_prep":
            prep.setdefault(s["step"], []).append(s)
    steps = sorted(k for k in prep if k)
    assert len(steps) == stats["steps"] == CHUNKS * (3 if depth == 1 else 4)
    carry = [s for s in spans if s["name"] == "carry_prior"]
    assert sorted(s["step"] for s in carry) == steps  # once a step, under its id
    drops = [s for s in spans if s["name"] == "late_drop"]
    # only a drain that dropped something records one: the serial loop's
    # drop-only drains (no batch formed: step 0), none with two in flight
    assert len(drops) == (CHUNKS if depth == 1 else 0)
    assert all(s["step"] == 0 for s in drops)
    for s in carry + drops:
        assert s["tx"] == ""
        assert any(p["start"] <= s["start"] and s["end"] <= p["end"] for p in prep[s["step"]]), s
