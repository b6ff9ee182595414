"""TxFlow engine end-to-end + golden parity (reference txflow/service_test.go
and the SURVEY §4 contract: batched device decisions == scalar reference path).
"""

import hashlib

import numpy as np
import pytest

from txflow_tpu.abci import AppConns, KVStoreApplication
from txflow_tpu.engine import TxExecutor, TxFlow
from txflow_tpu.pool import Mempool, TxVotePool
from txflow_tpu.store import MemDB, TxStore
from txflow_tpu.types import MockPV, TxVote, Validator, ValidatorSet
from txflow_tpu.utils.config import EngineConfig, MempoolConfig
from txflow_tpu.utils.events import EventBus, EventTx

CHAIN_ID = "txflow-test"
HEIGHT = 1


def make_pvs(n=4):
    pvs = sorted((MockPV() for _ in range(n)), key=lambda p: p.get_address())
    vals = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), 10) for pv in pvs])
    by_addr = {pv.get_address(): pv for pv in pvs}
    return [by_addr[v.address] for v in vals], vals


def make_engine(vals, app=None, use_device=True, max_batch=1024, verifier=None):
    conns = AppConns(app or KVStoreApplication())
    mempool = Mempool(MempoolConfig(cache_size=1000), conns.mempool)
    commitpool = Mempool(MempoolConfig(cache_size=1000))
    votepool = TxVotePool(MempoolConfig(cache_size=10000))
    tx_store = TxStore(MemDB())
    bus = EventBus()
    execu = TxExecutor(conns.consensus, mempool, event_bus=bus)
    flow = TxFlow(
        CHAIN_ID,
        HEIGHT,
        vals,
        votepool,
        mempool,
        commitpool,
        execu,
        tx_store,
        config=EngineConfig(max_batch=max_batch, use_device=use_device),
        verifier=verifier,
    )
    return flow, mempool, commitpool, votepool, tx_store, conns.app, bus


def sign_vote(pv, tx: bytes, height=HEIGHT, ts=1700000000_000000000) -> TxVote:
    v = TxVote(
        height=height,
        tx_hash=hashlib.sha256(tx).hexdigest().upper(),
        tx_key=hashlib.sha256(tx).digest(),
        timestamp_ns=ts,
        validator_address=pv.get_address(),
    )
    pv.sign_tx_vote(CHAIN_ID, v)
    return v


def test_end_to_end_commit_on_quorum():
    pvs, vals = make_pvs(4)
    flow, mempool, commitpool, votepool, tx_store, app, bus = make_engine(vals)
    sub = bus.subscribe(EventTx)

    txs = [b"k%d=v%d" % (i, i) for i in range(5)]
    for tx in txs:
        mempool.check_tx(tx)
    for tx in txs:
        for pv in pvs[:3]:  # exactly quorum: 30 >= 27
            votepool.check_tx(sign_vote(pv, tx))

    processed = flow.step()
    assert processed == 15

    # every tx committed: app saw it, commitpool holds it, store certifies it
    assert app.tx_count == 5
    assert app.state[b"k0"] == b"v0"
    assert commitpool.size() == 5
    assert mempool.size() == 0  # removed by executor commit/update
    for tx in txs:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        commit = tx_store.load_tx_commit(tx_hash)
        assert commit is not None and len(commit.commits) == 3
    # quorum votes purged from the pool, in-flight sets dropped
    assert votepool.size() == 0
    assert flow.vote_sets == {}
    # commit events fired per tx
    # commit events are fanned out by the executor's event worker thread
    # (off the commit path): collect with a timeout instead of an instant
    # drain
    events = []
    while len(events) < 5:
        ev = sub.get(timeout=5.0)
        assert ev is not None, f"only {len(events)} commit events arrived"
        events.append(ev)
    assert len(events) == 5 and events[0].data.tx == txs[0]


def test_no_commit_below_quorum():
    pvs, vals = make_pvs(4)
    flow, mempool, commitpool, votepool, tx_store, app, _ = make_engine(vals)
    tx = b"under=quorum"
    mempool.check_tx(tx)
    for pv in pvs[:2]:  # 20 < 27
        votepool.check_tx(sign_vote(pv, tx))
    flow.step()
    assert app.tx_count == 0
    assert commitpool.size() == 0
    assert votepool.size() == 2  # votes stay pending
    tx_hash = hashlib.sha256(tx).hexdigest().upper()
    assert flow.vote_sets[tx_hash].stake() == 20
    # third vote arrives in a later batch: quorum crosses using prior stake
    votepool.check_tx(sign_vote(pvs[2], tx))
    flow.step()
    assert app.tx_count == 1
    assert votepool.size() == 0


def test_byzantine_and_invalid_votes_rejected():
    pvs, vals = make_pvs(4)
    flow, mempool, _, votepool, _, app, _ = make_engine(vals)
    tx = b"target=1"
    mempool.check_tx(tx)

    good = sign_vote(pvs[0], tx)
    votepool.check_tx(good)
    # corrupt signature
    bad = sign_vote(pvs[1], tx)
    bad.signature = bad.signature[:-1] + bytes([bad.signature[-1] ^ 1])
    votepool.check_tx(bad)
    # non-validator vote
    stranger = MockPV()
    votepool.check_tx(sign_vote(stranger, tx))
    # conflicting second signature from validator 0 (different timestamp)
    conflict = sign_vote(pvs[0], tx, ts=1700000001_000000000)
    votepool.check_tx(conflict)

    flow.step()
    flow.step()  # second pass clears the conflicting leftover
    assert app.tx_count == 0
    tx_hash = hashlib.sha256(tx).hexdigest().upper()
    assert flow.vote_sets[tx_hash].stake() == 10  # only the good vote counted
    # bad votes were removed from the pool; the good one stays available
    # for gossip until its tx commits (reference purges only on commit)
    assert votepool.size() == 1
    assert votepool.has(__import__("txflow_tpu.pool.txvotepool", fromlist=["vote_key"]).vote_key(good))


def test_late_votes_for_committed_tx_are_dropped():
    pvs, vals = make_pvs(4)
    flow, mempool, _, votepool, tx_store, app, _ = make_engine(vals)
    tx = b"late=vote"
    mempool.check_tx(tx)
    for pv in pvs[:3]:
        votepool.check_tx(sign_vote(pv, tx))
    flow.step()
    assert app.tx_count == 1
    # the 4th vote arrives after commit
    votepool.check_tx(sign_vote(pvs[3], tx))
    flow.step()
    assert votepool.size() == 0
    assert app.tx_count == 1  # not re-committed
    assert flow.vote_sets == {}


def test_batched_matches_scalar_reference_engine():
    """Golden parity: identical commit decisions, app state and stores for a
    shuffled, adversarial vote stream (BASELINE config 4 in miniature)."""
    import random

    rng = random.Random(42)
    pvs, vals = make_pvs(7)  # total 70, quorum 47 -> 5 votes needed
    txs = [b"ptx%d=%d" % (i, i) for i in range(12)]

    stream = []
    for t_i, tx in enumerate(txs):
        n_votes = rng.randint(2, 7)
        voters = rng.sample(range(7), n_votes)
        for vi in voters:
            vote = sign_vote(pvs[vi], tx)
            if rng.random() < 0.15:  # corrupt some
                vote.signature = bytes(64)
            stream.append(vote)
    rng.shuffle(stream)

    # scalar reference engine: one vote at a time through add_vote
    flow_s, mem_s, commit_s, pool_s, store_s, app_s, _ = make_engine(vals, use_device=False)
    for tx in txs:
        mem_s.check_tx(tx)
    for v in stream:
        flow_s.try_add_vote(v.copy())

    # batched device engine: same stream via the pool, uneven batch sizes
    flow_b, mem_b, commit_b, pool_b, store_b, app_b, _ = make_engine(vals, max_batch=17)
    for tx in txs:
        mem_b.check_tx(tx)
    for v in stream:
        try:
            pool_b.check_tx(v)
        except Exception:
            pass
    while flow_b.step():
        pass

    assert app_b.tx_count == app_s.tx_count
    assert app_b.state == app_s.state
    assert app_b.digest == app_s.digest  # commit ORDER identical, not just set
    for tx in txs:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        cs, cb = store_s.load_tx_commit(tx_hash), store_b.load_tx_commit(tx_hash)
        assert (cs is None) == (cb is None)
        if cs is not None:
            assert {c.validator_address for c in cs.commits} == {
                c.validator_address for c in cb.commits
            }
    # uncommitted stake identical
    for tx_hash, vs in flow_s.vote_sets.items():
        assert flow_b.vote_sets[tx_hash].stake() == vs.stake()


def test_group_commit_matches_per_tx_commit():
    """EngineConfig.commit_interval > 1 (ABCI Commit fence amortized over a
    group of fast-path txs) must be observably identical to the reference-
    faithful per-tx path: same committed set, same app tx counts, same
    per-tx commit events, pools drained."""
    import hashlib as _h

    from txflow_tpu.node import LocalNet
    from txflow_tpu.utils.config import test_config as make_test_config
    from txflow_tpu.utils.events import EventTx

    results = {}
    for interval in (1, 4):
        cfg = make_test_config()
        cfg.engine.commit_interval = interval
        net = LocalNet(4, use_device_verifier=False, config=cfg)
        events = [[] for _ in net.nodes]
        for i, node in enumerate(net.nodes):
            node.event_bus.subscribe_callback(
                EventTx, (lambda lst: (lambda ev: lst.append(ev.data.tx_hash)))(events[i])
            )
        net.start()
        try:
            txs = [b"gc%d-%d=v" % (interval, i) for i in range(10)]
            for tx in txs:
                net.broadcast_tx(tx)
            assert net.wait_all_committed(txs, timeout=60)
            hashes = sorted(_h.sha256(tx).hexdigest().upper() for tx in txs)
            for i, node in enumerate(net.nodes):
                for h in hashes:
                    assert node.tx_store.load_tx_votes(h), (interval, h)
                assert sorted(events[i]) == hashes, (interval, i)
            results[interval] = {
                "tx_counts": sorted(n.app.tx_count for n in net.nodes),
                "committed": sorted(
                    int(n.metrics.committed_txs.value()) for n in net.nodes
                ),
            }
        finally:
            net.stop()
    assert results[1] == results[4], results


def test_quorum_before_tx_defers_apply_until_bytes_arrive():
    """A vote quorum can land (gossip) before the tx bytes reach the
    local mempool. The certificate must persist immediately, but the
    ABCI apply must DEFER until the bytes arrive — not be silently
    skipped (r5 soak: post-partition churn left a node with the
    certificate, no apply, and claim_vtx blocking the block path's
    delivery too — permanent state divergence)."""
    import hashlib as _h
    import time as _t

    from txflow_tpu.node import LocalNet

    # mempool gossip OFF: tx bytes only exist where we put them
    net = LocalNet(4, use_device_verifier=False, mempool_broadcast=False)
    net.start()
    try:
        tx = b"late-bytes=v"
        tx_hash = _h.sha256(tx).hexdigest().upper()
        # nodes 1-3 get the tx (and their signers vote); node 0 does NOT
        for node in net.nodes[1:]:
            node.mempool.check_tx(tx)
        deadline = _t.monotonic() + 30
        while _t.monotonic() < deadline:
            if all(n.tx_store.has_tx(tx_hash) for n in net.nodes):
                break
            _t.sleep(0.02)
        # every node holds the certificate (3/4 quorum formed via gossip)
        for n in net.nodes:
            assert n.tx_store.has_tx(tx_hash), "certificate missing"
        # nodes 1-3 applied; node 0 must have DEFERRED, not dropped
        deadline = _t.monotonic() + 10
        while _t.monotonic() < deadline:
            if all(n.app.state.get(b"late-bytes") == b"v" for n in net.nodes[1:]):
                break
            _t.sleep(0.02)
        for n in net.nodes[1:]:
            assert n.app.state.get(b"late-bytes") == b"v"
        assert net.nodes[0].app.state.get(b"late-bytes") is None
        assert tx_hash in net.nodes[0].txflow._unapplied

        # the bytes arrive late: the committer retry applies them
        net.nodes[0].mempool.check_tx(tx)
        deadline = _t.monotonic() + 15
        while _t.monotonic() < deadline:
            if net.nodes[0].app.state.get(b"late-bytes") == b"v":
                break
            _t.sleep(0.02)
        assert net.nodes[0].app.state.get(b"late-bytes") == b"v", (
            "deferred apply never ran after the bytes arrived"
        )
        assert tx_hash not in net.nodes[0].txflow._unapplied
    finally:
        net.stop()


def test_block_claim_before_committer_wake_credits_apply_once():
    """A quorum decided without tx bytes is queued for the committer AND
    registered as unapplied; if a block claims the delivery (claim_vtx)
    before the committer wake processes the queued item, the apply credit
    must be taken exactly once — double-counting let commits_drained()
    report True while later decided commits were still queued (r5
    review)."""
    from txflow_tpu.types import TxVoteSet

    pvs, vals = make_pvs(4)
    flow, mempool, commitpool, votepool, tx_store, app, _ = make_engine(
        vals, use_device=False
    )
    # no flow.start(): the committer wake is driven by hand below
    tx = b"claimrace=1"  # bytes NEVER enter the mempool
    tx_hash = hashlib.sha256(tx).hexdigest().upper()
    vs = TxVoteSet(CHAIN_ID, HEIGHT, tx_hash, hashlib.sha256(tx).digest(), vals)
    for pv in pvs[:3]:
        added, err = vs.add_vote(sign_vote(pv, tx))
        assert added, err
    with flow._mtx:
        flow._enqueue_commit(vs)
    assert flow._decided_count == 1 and tx_hash in flow._unapplied

    # block path claims the delivery first (this credits the apply)
    assert flow.claim_vtx(tx) is True
    assert flow._applied_count == 1

    # the committer wake now processes the stale queued item: it must NOT
    # credit the apply again
    item = flow._commit_q.get_nowait()
    flow._commit_batch([item], purge=[], interval=1)
    assert flow._applied_count == 1, "apply credited twice for one decision"

    # a second, normal decision must still be visibly un-drained until its
    # own wake applies it
    tx2 = b"claimrace=2"
    mempool.check_tx(tx2)
    tx2_hash = hashlib.sha256(tx2).hexdigest().upper()
    vs2 = TxVoteSet(CHAIN_ID, HEIGHT, tx2_hash, hashlib.sha256(tx2).digest(), vals)
    for pv in pvs[:3]:
        vs2.add_vote(sign_vote(pv, tx2))
    with flow._mtx:
        flow._enqueue_commit(vs2)
    assert not flow.commits_drained(), (
        "drained while a decided commit is still queued"
    )
    item2 = flow._commit_q.get_nowait()
    flow._commit_batch([item2], purge=[], interval=1)
    assert flow._applied_count == 2 == flow._decided_count
    assert app.tx_count == 1  # only tx2 applied here (tx1 went to a block)


def test_more_validators_than_hosted_nodes_commit():
    """BASELINE configs 2-3 topology: a 16-entry validator set hosted by
    only 4 full nodes; the other validators' votes arrive pregenerated
    (as if gossiped from remote peers). Every hosted node must still
    commit every tx — quorum is 2/3 of the WHOLE set's stake."""
    from txflow_tpu.node import LocalNet

    pvs, vals = make_pvs(16)
    net = LocalNet(
        chain_id=CHAIN_ID,
        use_device_verifier=False,
        priv_vals=pvs,
        sign=False,
        mempool_broadcast=False,
        n_nodes=4,
    )
    assert len(net.nodes) == 4 and net.val_set.size() == 16
    txs = [b"mv%d=v" % i for i in range(10)]
    votes = [sign_vote(pv, tx, height=0) for tx in txs for pv in pvs[:11]]
    net.start()
    try:
        for nd in net.nodes:
            nd.mempool.check_tx_many(txs)
        # votes enter round-robin across hosted nodes (the bench's
        # injection shape); gossip fans them out
        for vi in range(11):
            net.nodes[vi % 4].tx_vote_pool.check_tx_many(
                [v for v in votes if v.validator_address == pvs[vi].get_address()]
            )
        assert net.wait_all_committed(txs, timeout=30)
        for nd in net.nodes:
            for tx in txs:
                h = hashlib.sha256(tx).hexdigest().upper()
                cert = nd.tx_store.load_tx_commit(h)
                assert cert is not None and len(cert.commits) == 11
    finally:
        net.stop()
