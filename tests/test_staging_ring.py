"""Double-buffered device readback (parallel.staging.StagingRing).

The ring changes WHERE the packed-result ``np.asarray`` runs — a
dedicated readback thread instead of the ticket waiter — never what it
reads, so every decision and certificate must stay byte-identical to the
synchronous path. Covered here:

- ring semantics: eager readback, overlap (hidden_s) accounting,
  depth overflow degrading to a synchronous non-blocking readback,
  error capture + re-raise at the waiter, close drains queued slots
  and post-close submits degrade to synchronous;
- certificate byte-parity: a device engine with the staging ring on
  commits byte-identical certificates to the scalar ``try_add_vote``
  golden path;
- drain-on-stop: stopping an engine with staged readbacks in flight
  settles every slot (in_flight back to 0).
"""

import hashlib
import time

import numpy as np
import pytest

from test_pipeline import (
    _wait_quiescent,
    make_engine as make_threaded_engine,
    make_pvs,
    sign_vote,
)
from txflow_tpu.parallel.staging import StagingRing, StageSlot
from txflow_tpu.verifier import DeviceVoteVerifier

BUCKETS = (8, 32)  # CPU-sized compiles (same ladder as test_mesh_engine)


# ---- ring unit semantics ----------------------------------------------


def test_ring_eager_readback_and_overlap_accounting():
    """A submitted slot is read back WITHOUT the caller waiting; the
    overlap ledger credits readback seconds the caller never blocked
    on (hidden_s), and result() returns the host bytes."""
    ring = StagingRing(depth=2, name="t-eager")
    try:
        arr = np.arange(64, dtype=np.int64)
        slot = ring.submit(arr)
        # the readback thread lands the transfer with no result() call
        assert slot._done.wait(timeout=5.0), "eager readback never ran"
        time.sleep(0.01)  # caller does "work" the readback hid under
        host = ring.result(slot)
        np.testing.assert_array_equal(host, arr)
        stats = ring.stats()
        assert stats["slots_total"] == 1
        assert stats["in_flight"] == 0
        assert stats["readback_s"] >= 0.0
        # waited ~0 while the readback had already landed: every
        # readback second counts as hidden
        assert stats["hidden_s"] <= stats["readback_s"] + 1e-9
    finally:
        ring.close()


def test_ring_depth_overflow_degrades_to_synchronous():
    """More un-awaited submits than ``depth`` NEVER block the submitter:
    the overflow readback runs synchronously on the caller (buffers stay
    bounded by degradation). Blocking would deadlock engines sharing the
    ring — each fills ahead of its own collector on one loop thread, so
    every permit holder can end up parked in submit() at once while the
    result() calls that release permits never run."""
    ring = StagingRing(depth=1, name="t-depth")
    try:
        first = ring.submit(np.zeros(4))
        # full ring: the second submit returns an already-landed slot
        second = ring.submit(np.ones(4))
        assert second._done.is_set(), "overflow submit did not run inline"
        assert not second._queued
        np.testing.assert_array_equal(ring.result(second), np.ones(4))
        np.testing.assert_array_equal(ring.result(first), np.zeros(4))
        stats = ring.stats()
        assert stats["sync_readbacks"] == 1
        assert stats["slots_total"] == 2
        assert stats["in_flight"] == 0
        # the sync slot held no permit: result(second) must not inflate
        # the semaphore, so the freed ring stages the next submit again
        third = ring.submit(np.full(4, 2))
        assert third._queued
        np.testing.assert_array_equal(ring.result(third), np.full(4, 2))
        assert ring.stats()["sync_readbacks"] == 1
    finally:
        ring.close()


def test_ring_error_captured_and_reraised_at_waiter():
    """A readback that raises surfaces at result(), not in the thread —
    and the ring keeps serving later slots."""

    class Boom:
        def __array__(self, dtype=None):
            raise RuntimeError("device readback failed")

    ring = StagingRing(depth=2, name="t-error")
    try:
        bad = ring.submit(Boom())
        with pytest.raises(RuntimeError, match="device readback failed"):
            ring.result(bad)
        good = ring.submit(np.full(3, 7))
        np.testing.assert_array_equal(ring.result(good), np.full(3, 7))
    finally:
        ring.close()


def test_ring_close_drains_and_degrades_to_synchronous():
    """close() completes already-queued slots (their waiters still get
    bytes); submits after close run synchronously — the drain path is
    never lossy."""
    ring = StagingRing(depth=4, name="t-close")
    queued = [ring.submit(np.full(2, i)) for i in range(3)]
    ring.close()
    for i, slot in enumerate(queued):
        np.testing.assert_array_equal(ring.result(slot), np.full(2, i))
    late = ring.submit(np.full(2, 9))  # post-close: synchronous slot
    np.testing.assert_array_equal(ring.result(late), np.full(2, 9))
    ring.close()  # idempotent


# ---- engine-level parity + drain --------------------------------------


def _quorum_stream(pvs, txs, corrupt_every=7):
    stream = []
    for i, tx in enumerate(txs):
        for vi, pv in enumerate(pvs):
            vote = sign_vote(pv, tx)
            if (i + vi) % corrupt_every == 0:
                vote.signature = bytes(64)
            stream.append(vote)
    return stream


def test_staged_engine_certificates_match_golden():
    """Device engine with the staging ring on: certificates, app state,
    and commit order byte-identical to the scalar try_add_vote golden
    path — and the run actually staged readbacks (slots_total > 0)."""
    pvs, vals = make_pvs(4)
    txs = [b"sr%d=%d" % (i, i) for i in range(24)]
    stream = _quorum_stream(pvs, txs)

    flow_s, mem_s, _, store_s, app_s = make_threaded_engine(
        vals, use_device=False
    )
    for tx in txs:
        mem_s.check_tx(tx)
    for v in stream:
        flow_s.try_add_vote(v.copy())

    verifier = DeviceVoteVerifier(vals, buckets=BUCKETS, staging_ring=2)
    verifier.warmup(full=True)  # compile outside the drain-wait windows
    flow_d, mem_d, pool_d, store_d, app_d = make_threaded_engine(
        vals, verifier=verifier, max_batch=32, min_batch=4,
        pipeline_depth=2, coalesce=True, coalesce_linger=0.02,
    )
    for tx in txs:
        mem_d.check_tx(tx)
    flow_d.start()
    try:
        for v in stream:
            try:
                pool_d.check_tx(v)
            except Exception:
                pass  # cache dup (zeroed sigs share a vote key)
        assert _wait_quiescent(flow_d, pool_d, timeout=90.0), (
            "staged engine never drained"
        )
        stats = flow_d.pipeline_stats()
    finally:
        flow_d.stop()

    ring = stats.get("staging")
    assert ring is not None and ring["slots_total"] > 0, (
        "run never staged a readback — parity test is vacuous"
    )
    assert app_d.tx_count == app_s.tx_count
    assert app_d.state == app_s.state
    assert app_d.digest == app_s.digest  # commit ORDER identical
    committed = 0
    for tx in txs:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        cs = store_s.load_tx_commit(tx_hash)
        cd = store_d.load_tx_commit(tx_hash)
        assert (cs is None) == (cd is None)
        if cs is not None:
            committed += 1
            assert [
                (c.validator_address, c.signature) for c in cs.commits
            ] == [(c.validator_address, c.signature) for c in cd.commits]
    assert committed > 0, "stream never formed a quorum — test is vacuous"


def test_stop_drains_staged_slots():
    """stop() with staged readbacks in flight: every slot settles
    (in_flight 0) and the depth gauge reads 0: the drain must reach
    every in-flight ticket's result."""
    pvs, vals = make_pvs(4)
    verifier = DeviceVoteVerifier(vals, buckets=BUCKETS, staging_ring=2)
    verifier.warmup(full=True)
    flow, mempool, votepool, store, app = make_threaded_engine(
        vals, verifier=verifier, max_batch=32, min_batch=4,
        pipeline_depth=4, coalesce=True, coalesce_linger=0.01,
    )
    txs = [b"sd%d=v" % i for i in range(40)]
    votes = [sign_vote(pv, tx) for tx in txs for pv in pvs[:3]]
    for tx in txs:
        mempool.check_tx(tx)
    flow.start()
    try:
        for v in votes:
            votepool.check_tx(v)
    finally:
        # stop with work still flowing: the run loop's finally block
        # must collect the staged in-flight tail
        flow.stop()

    assert flow.metrics.pipeline_depth.value() == 0, "orphaned tickets"
    ring = verifier.staging_stats()
    if ring is not None:  # the run may stop before the first dispatch
        assert ring["in_flight"] == 0, "staged slot leaked past stop()"
