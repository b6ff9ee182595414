"""Pipelined verify engine: submit/collect overlap, parity, drain, shapes.

Covers the verify pipeline introduced for overlap of host prep, device
verify, and commit routing:

- randomized parity: the threaded pipelined engine (pipeline_depth >= 2)
  produces BYTE-identical commit certificates and commit order to the
  scalar ``try_add_vote`` golden path;
- drain-on-stop: ``stop()`` collects every in-flight ticket — no lost
  votes, pipeline-depth gauge back to 0;
- step accounting: ``step()`` returns decided + dropped, and
  ``last_step_stats`` reconciles decided + requeued == verified batch;
- ShapeWarmRegistry: prewarm covers every shape a run dispatches
  (compile_in_run() False), cold dispatches are detected;
- async submit surface: the ResilientVoteVerifier collect-time
  fallback (FlakyVerifier fail_at="result").
"""

import hashlib
import random
import time

import numpy as np
import pytest

from txflow_tpu.abci import AppConns, KVStoreApplication
from txflow_tpu.engine import ShapeWarmRegistry, TxExecutor, TxFlow
from txflow_tpu.faults import FlakyVerifier
from txflow_tpu.pool import Mempool, TxVotePool
from txflow_tpu.store import MemDB, TxStore
from txflow_tpu.types import MockPV, TxVote, Validator, ValidatorSet
from txflow_tpu.types.tx_vote import canonical_sign_bytes
from txflow_tpu.utils.config import EngineConfig, MempoolConfig
from txflow_tpu.utils.events import EventBus
from txflow_tpu.verifier import (
    ResilientVoteVerifier,
    ScalarVoteVerifier,
)

CHAIN_ID = "txflow-test"
HEIGHT = 1


def make_pvs(n=4):
    pvs = sorted((MockPV() for _ in range(n)), key=lambda p: p.get_address())
    vals = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), 10) for pv in pvs])
    by_addr = {pv.get_address(): pv for pv in pvs}
    return [by_addr[v.address] for v in vals], vals


def make_engine(vals, use_device=True, verifier=None, **cfg_kw):
    conns = AppConns(KVStoreApplication())
    mempool = Mempool(MempoolConfig(cache_size=4000), conns.mempool)
    commitpool = Mempool(MempoolConfig(cache_size=4000))
    votepool = TxVotePool(MempoolConfig(cache_size=20000))
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
        config=EngineConfig(use_device=use_device, **cfg_kw),
        verifier=verifier,
    )
    return flow, mempool, votepool, tx_store, conns.app


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


def _mixed_stream(pvs, txs, seed):
    """Randomized vote stream: <=1 vote per (tx, validator), ~15%
    corrupted signatures, plus stranger (non-validator) votes."""
    rng = random.Random(seed)
    stranger = MockPV()
    stream = []
    for tx in txs:
        voters = rng.sample(range(len(pvs)), rng.randint(2, len(pvs)))
        for vi in voters:
            vote = sign_vote(pvs[vi], tx)
            if rng.random() < 0.15:
                vote.signature = bytes(64)  # byzantine: garbage signature
            stream.append(vote)
        if rng.random() < 0.3:
            stream.append(sign_vote(stranger, tx))
    rng.shuffle(stream)
    return stream


def _wait_quiescent(flow, votepool, timeout=30.0):
    """Wait until the threaded engine has visited every pool entry, holds
    no retries, and drained its commit queue — twice in a row, so a batch
    formed between the checks can't fake quiescence."""
    deadline = time.monotonic() + timeout
    stable = 0
    while time.monotonic() < deadline:
        idle = (
            flow._drain_cursor >= votepool.seq()
            and not flow._retry
            and flow.commits_drained()
        )
        stable = stable + 1 if idle else 0
        if stable >= 3:
            return True
        time.sleep(0.02)
    return False


@pytest.mark.parametrize("seed", [11, 23])
def test_pipelined_matches_scalar_golden_path(seed):
    """Commit certificates from the threaded pipelined engine are
    BYTE-identical (same signatures, same order) to the scalar
    ``try_add_vote`` reference, for a shuffled honest/byzantine stream."""
    pvs, vals = make_pvs(7)  # total 70, quorum 47 -> 5 votes needed
    txs = [b"pp%d-%d=%d" % (seed, i, i) for i in range(14)]
    stream = _mixed_stream(pvs, txs, seed)

    # scalar golden path: one vote at a time through try_add_vote
    flow_s, mem_s, _, store_s, app_s = make_engine(vals, use_device=False)
    for tx in txs:
        mem_s.check_tx(tx)
    for v in stream:
        flow_s.try_add_vote(v.copy())

    # pipelined engine: same stream via the pool, threaded run loop with
    # tickets in flight; small batches force many overlapping steps
    flow_p, mem_p, pool_p, store_p, app_p = make_engine(
        vals,
        use_device=False,
        max_batch=17,
        min_batch=1,
        pipeline_depth=3,
    )
    for tx in txs:
        mem_p.check_tx(tx)
    flow_p.start()
    try:
        for v in stream:
            try:
                pool_p.check_tx(v)
            except Exception:
                pass  # cache dup etc. — the scalar path saw the vote anyway
        assert _wait_quiescent(flow_p, pool_p), "pipelined engine never drained"
    finally:
        flow_p.stop()

    assert app_p.tx_count == app_s.tx_count
    assert app_p.state == app_s.state
    assert app_p.digest == app_s.digest  # commit ORDER identical
    for tx in txs:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        cs = store_s.load_tx_commit(tx_hash)
        cp = store_p.load_tx_commit(tx_hash)
        assert (cs is None) == (cp is None)
        if cs is not None:
            # byte-identical certificates: same validators, same
            # signatures, same order
            assert [
                (c.validator_address, c.signature) for c in cs.commits
            ] == [(c.validator_address, c.signature) for c in cp.commits]
    for tx_hash, vs in flow_s.vote_sets.items():
        assert flow_p.vote_sets[tx_hash].stake() == vs.stake()
    stats = flow_p.pipeline_stats()
    assert stats["depth"] == 3 and stats["steps"] > 0


def test_stop_drains_inflight_tickets():
    """stop() must collect and route every in-flight ticket: the depth
    gauge reads 0, and every injected vote is either decided or still in
    the pool (none lost)."""
    pvs, vals = make_pvs(4)
    flow, mempool, votepool, store, app = make_engine(
        vals,
        use_device=False,
        max_batch=8,
        min_batch=1,
        pipeline_depth=4,
    )
    txs = [b"drain%d=v" % i for i in range(50)]
    votes = [sign_vote(pv, tx) for tx in txs for pv in pvs[:3]]
    for tx in txs:
        mempool.check_tx(tx)
    flow.start()
    try:
        for v in votes:
            votepool.check_tx(v)
    finally:
        # stop with work still flowing: the run loop's finally block must
        # drain the in-flight tail
        flow.stop()

    assert flow.metrics.pipeline_depth.value() == 0, "orphaned tickets"
    # no vote lost: whatever was not decided is still in the pool or the
    # retry set, so serial steps can finish the job deterministically
    while flow.step():
        pass
    assert app.tx_count == len(txs)
    for tx in txs:
        cert = store.load_tx_commit(hashlib.sha256(tx).hexdigest().upper())
        assert cert is not None and len(cert.commits) == 3


def test_step_accounting_reconciles():
    """step() returns decided + dropped; requeued votes are NOT counted
    until the step that decides them, and last_step_stats always
    reconciles decided + requeued == verified batch size."""
    pvs, vals = make_pvs(4)
    flow, mempool, votepool, _, app = make_engine(vals, use_device=False)
    tx = b"acct=1"
    mempool.check_tx(tx)
    for pv in pvs[:3]:
        votepool.check_tx(sign_vote(pv, tx))
    # conflicting second vote from validator 0 (same (tx, validator), new
    # timestamp): the in-batch first-occurrence mask defers it to _retry
    votepool.check_tx(sign_vote(pvs[0], tx, ts=1700000001_000000000))

    got = flow.step()
    s = flow.last_step_stats
    assert s["batch"] == 4
    assert s["decided"] + s["requeued"] == s["batch"]
    assert s["requeued"] == 1  # the in-batch duplicate
    assert got == s["decided"] + s["dropped"] == 3
    assert app.tx_count == 1  # quorum 30 >= 27 committed

    # the requeued conflict's tx has committed meanwhile, so the next
    # step drops it at DRAIN time (late vote, never re-verified): counted
    # once, as a drop, not as a decision
    got2 = flow.step()
    s2 = flow.last_step_stats
    assert s2 == {"decided": 0, "requeued": 0, "dropped": 1, "batch": 0}
    assert got2 == 1
    total = s["decided"] + s2["decided"] + s["dropped"] + s2["dropped"]
    assert total == 4, "every vote counted exactly once across steps"
    while flow.step():
        pass  # terminates: no votes left
    assert votepool.size() == 0


@pytest.mark.slow
def test_shape_warm_registry_covers_run():
    """prewarm() compiles and snapshots every reachable shape; a dispatch
    inside the covered envelope is compile-free (compile_in_run() False),
    and an unwarmed verifier's dispatch is flagged cold."""
    from txflow_tpu.verifier import DeviceVoteVerifier

    pvs, vals = make_pvs(4)
    ver = DeviceVoteVerifier(vals, buckets=(8,))
    reg = ShapeWarmRegistry(ver)
    warm = reg.prewarm(full=True)
    assert warm, "prewarm recorded no shapes"
    # the prediction mirrors warmup's coverage: everything it enumerates
    # was actually dispatched
    assert set(reg.enumerate_shapes(full=True)) <= set(warm)

    # a real batch inside the warmed envelope: no cold compile
    msgs, sigs, vidx, slot = [], [], [], []
    for t in range(2):
        tx_hash = hashlib.sha256(b"shape-tx%d" % t).hexdigest().upper()
        for vi, pv in enumerate(pvs):
            v = TxVote(
                height=HEIGHT,
                tx_hash=tx_hash,
                tx_key=hashlib.sha256(b"shape-tx%d" % t).digest(),
                timestamp_ns=1700000000_000000000,
                validator_address=pv.get_address(),
            )
            pv.sign_tx_vote(CHAIN_ID, v)
            msgs.append(canonical_sign_bytes(CHAIN_ID, HEIGHT, tx_hash, v.timestamp_ns))
            sigs.append(v.signature)
            vidx.append(vi)
            slot.append(t)
    res = ver.verify_and_tally(msgs, sigs, np.array(vidx), np.array(slot), 2)
    assert bool(res.valid.all())
    assert reg.cold_shapes() == []
    assert reg.compile_in_run() is False

    # an unwarmed registry flags the same dispatch as an in-run compile
    ver2 = DeviceVoteVerifier(vals, buckets=(8,))
    reg2 = ShapeWarmRegistry(ver2)  # no prewarm
    ver2.verify_and_tally(msgs, sigs, np.array(vidx), np.array(slot), 2)
    assert reg2.compile_in_run() is True


def test_engine_prewarms_shapes_on_start():
    """EngineConfig.prewarm_shapes builds the registry at start() so no
    shape compiles inside the pipeline (scalar verifier degrades to the
    empty shape set, exercising the gate cheaply in tier-1)."""
    pvs, vals = make_pvs(4)
    flow, mempool, votepool, _, app = make_engine(
        vals, use_device=False, prewarm_shapes=True
    )
    flow.start()
    try:
        assert flow._shape_registry is not None
        assert flow._shape_registry.cold_shapes() == []
        tx = b"prewarm=v"
        mempool.check_tx(tx)
        for pv in pvs[:3]:
            votepool.check_tx(sign_vote(pv, tx))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and app.tx_count < 1:
            time.sleep(0.01)
        assert app.tx_count == 1
    finally:
        flow.stop()


def _rig_batch(pvs, vals, n_txs=2):
    by_addr = {pv.get_address(): pv for pv in pvs}
    msgs, sigs, vidx, slot = [], [], [], []
    for t in range(n_txs):
        tx_hash = hashlib.sha256(b"rig-tx%d" % t).hexdigest().upper()
        for vi, val in enumerate(vals.validators):
            v = TxVote(
                height=HEIGHT,
                tx_hash=tx_hash,
                tx_key=hashlib.sha256(b"rig-tx%d" % t).digest(),
                timestamp_ns=1700000000_000000000 + t,
                validator_address=val.address,
            )
            by_addr[val.address].sign_tx_vote(CHAIN_ID, v)
            msgs.append(canonical_sign_bytes(CHAIN_ID, HEIGHT, tx_hash, v.timestamp_ns))
            sigs.append(v.signature)
            vidx.append(vi)
            slot.append(t)
    return (msgs, sigs, np.array(vidx), np.array(slot), n_txs)


def _assert_same(result, golden):
    np.testing.assert_array_equal(result.valid, golden.valid)
    np.testing.assert_array_equal(result.stake, golden.stake)
    np.testing.assert_array_equal(result.maj23, golden.maj23)


def test_resilient_collect_failure_falls_back():
    """A ticket whose READBACK fails (FlakyVerifier fail_at='result')
    must surface the degradation policy at collect time: the batch is
    re-served via the blocking policy path and the error is recorded."""
    pvs, vals = make_pvs(4)
    batch = _rig_batch(pvs, vals)
    golden = ScalarVoteVerifier(vals).verify_and_tally(*batch)

    flaky = FlakyVerifier(
        ScalarVoteVerifier(vals), fail_calls=(0,), fail_at="result"
    )
    r = ResilientVoteVerifier(
        flaky,
        fallback=ScalarVoteVerifier(vals),
        max_attempts=2,
        backoff_base=0.001,
        sleep=lambda _s: None,
    )
    ticket = r.submit(*batch)  # dispatch succeeds; readback will fail
    _assert_same(ticket.result(), golden)
    assert r.device_failures >= 1
    assert flaky.calls >= 2, "policy re-run never went back to the device"
    assert r.device_healthy  # the re-run succeeded on the device lane

    # dispatch-time failure degrades the same way
    flaky2 = FlakyVerifier(
        ScalarVoteVerifier(vals), fail_calls=(0,), fail_at="submit"
    )
    r2 = ResilientVoteVerifier(
        flaky2,
        fallback=ScalarVoteVerifier(vals),
        max_attempts=2,
        backoff_base=0.001,
        sleep=lambda _s: None,
    )
    _assert_same(r2.submit(*batch).result(), golden)
    assert r2.device_failures >= 1


def test_segs_for_tx_indexed():
    """The per-tx index returns exactly the live votes for one tx, in
    insertion order, and stays consistent through remove/update/flush."""
    from txflow_tpu.pool.txvotepool import vote_key

    pvs, vals = make_pvs(4)
    pool = TxVotePool(MempoolConfig(cache_size=1000))
    tx_a, tx_b = b"seg-a=v", b"seg-b=v"
    votes_a = [sign_vote(pv, tx_a) for pv in pvs]
    votes_b = [sign_vote(pv, tx_b) for pv in pvs[:2]]
    for v in votes_a + votes_b:
        pool.check_tx(v)
    h_a = hashlib.sha256(tx_a).hexdigest().upper()
    h_b = hashlib.sha256(tx_b).hexdigest().upper()
    assert pool.segs_for_tx(h_a) == [v._seg_cache for v in votes_a]
    assert pool.segs_for_tx(h_b) == [v._seg_cache for v in votes_b]
    assert pool.segs_for_tx(h_a, limit=2) == [v._seg_cache for v in votes_a[:2]]
    assert pool.segs_for_tx("NOPE") == []

    pool.remove([vote_key(votes_a[0])])
    assert pool.segs_for_tx(h_a) == [v._seg_cache for v in votes_a[1:]]
    pool.update(1, votes_a[1:])
    assert pool.segs_for_tx(h_a) == []
    assert pool._by_tx.get(h_a) is None  # empty buckets are pruned
    assert pool.segs_for_tx(h_b) == [v._seg_cache for v in votes_b]
    pool.flush()
    assert pool.segs_for_tx(h_b) == []
    assert pool._by_tx == {}
