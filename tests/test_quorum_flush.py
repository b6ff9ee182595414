"""The coalescer's hold ends once the held votes complete a quorum.

A partial batch is held for ``coalesce_linger`` (or until the pool goes
idle) so that more votes can join it. Once the held votes, with the stake
already routed into the tx's vote set, reach the quorum by stake, nothing
still to come can change that tx's outcome: the hold ends at once (a quorum
flush). These cases pin that rule down:

1. the coalescer asks its probe only while it holds a partial batch, never
   where a full bucket is handed out, and a false probe leaves the deadline
   and idle paths as they were;
2. in the engine, a quorum pooled in one frame commits long before the
   linger, with the certificate of the scalar golden path;
3. the probe counts stake, not votes: with a long-tailed stake a frame in
   which two thirds of the validators have voted flushes nothing, the frame
   whose stake crosses flushes;
4. a backlog that covers a bucket never reaches the probe.
"""

import hashlib
import time

import pytest

from test_coalesce import FakeClock
from test_pipeline import make_engine, make_pvs, sign_vote
from txflow_tpu.engine.txflow import _BatchCoalescer
from txflow_tpu.faults.stake import stake_distribution
from txflow_tpu.pool.mempool import LANE_BULK, LANE_PRIORITY
from txflow_tpu.types import MockPV, Validator, ValidatorSet
from txflow_tpu.verifier import ScalarVoteVerifier


class Probe:
    def __init__(self, *answers):
        self.answers = list(answers)
        self.calls = 0

    def __call__(self):
        self.calls += 1
        return self.answers.pop(0) if self.answers else False


class Spans:
    active = True

    def __init__(self):
        self.got = []

    def span(self, tx, name, t0, t1, step):
        self.got.append((name, t0, t1, step))


# ---- the coalescer ----------------------------------------------------


def test_probe_is_not_asked_where_a_full_bucket_is_handed_out():
    probe = Probe(True, True, True)
    co = _BatchCoalescer((8, 32), cap=64, min_batch=4, linger=0.5,
                         clock=FakeClock(), probe=probe)
    assert co.decide(0) == 0
    assert co.decide(9) == 8
    assert co.decide(70) == 32
    assert probe.calls == 0
    assert (co.full_batches, co.quorum_flushes, co.linger_flushes) == (2, 0, 0)


def test_hold_ends_on_the_probe_and_counts_a_quorum_flush():
    clk, spans = FakeClock(), Spans()
    probe = Probe(False, True)
    co = _BatchCoalescer((8,), cap=64, min_batch=1, linger=0.5, clock=clk,
                         tracer=spans, probe=probe)
    assert co.decide(3, step=7) == 0  # the hold begins; no quorum yet
    assert co.holding
    t_hold = clk.t
    clk.t += 0.001
    assert co.decide(5, step=7) == 5  # the held votes complete a quorum
    assert probe.calls == 2
    assert (co.quorum_flushes, co.linger_flushes) == (1, 0)
    assert not co.holding
    # the hold is one linger span, and the batch's pickup ends where it began
    assert spans.got == [("linger_bulk", t_hold, clk.t, 7)]
    assert co.flush_t0 == t_hold


def test_false_probe_keeps_the_deadline_and_the_idle_flush():
    clk = FakeClock()
    probe = Probe()
    co = _BatchCoalescer((8,), cap=64, min_batch=1, linger=0.5, clock=clk,
                         probe=probe)
    assert co.decide(3) == 0
    clk.t += 0.3
    assert co.decide(3) == 0
    clk.t += 0.3
    assert co.decide(3) == 3  # deadline: flushed without asking again
    assert probe.calls == 2
    assert co.decide(2) == 0
    co.note_idle()
    assert co.decide(2) == 2  # idle: flushed without asking
    assert probe.calls == 3
    assert (co.linger_flushes, co.quorum_flushes) == (2, 0)


# ---- the engine -------------------------------------------------------


def _ladder_verifier(vals, sizes=None):
    """A scalar verifier with a bucket ladder: the engine coalesces on it
    (targets 16 and 64 at min_batch 4), and ``sizes`` records each batch."""
    verifier = ScalarVoteVerifier(vals)
    verifier.buckets = (16, 64)
    if sizes is not None:
        inner = verifier.verify_and_tally

        def spy(msgs, *a, **kw):
            sizes.append(len(msgs))
            return inner(msgs, *a, **kw)

        verifier.verify_and_tally = spy
    return verifier


def _holding_engine(vals, verifier, **kw):
    """A threaded engine whose holds end only on the probe: a long linger,
    no idle flush, and pool waits (whose timeout also reads as idle) longer
    than any pause between a test's frames."""
    cfg = dict(max_batch=64, min_batch=4, coalesce=True, coalesce_linger=5.0,
               idle_flush=0.0, poll_interval=0.5)
    cfg.update(kw)
    return make_engine(vals, use_device=False, verifier=verifier, **cfg)


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


def _cert(store, tx):
    commit = store.load_tx_commit(hashlib.sha256(tx).hexdigest().upper())
    assert commit is not None
    return [(c.validator_address, c.signature, c.timestamp_ns) for c in commit.commits]


def _golden(vals, txs, votes):
    flow, mem, _, store, _ = make_engine(vals, use_device=False)
    for tx in txs:
        mem.check_tx(tx)
    for v in votes:
        flow.try_add_vote(v.copy())
    return store


@pytest.mark.parametrize("lane", ["bulk", "prio"])
def test_a_pooled_quorum_commits_inside_the_linger(lane):
    """4 equal validators: 3 votes pooled in one frame are 30 of 40, over
    2/3. The hold ends as they land, not 0.5 s later, on the lane that
    holds them; the certificate is the scalar golden path's."""
    pvs, vals = make_pvs(4)
    tx = b"qf-%s=1" % lane.encode()
    votes = [sign_vote(pv, tx) for pv in pvs[:3]]
    golden = _golden(vals, [tx], votes)

    flow, mem, pool, store, app = _holding_engine(
        vals, _ladder_verifier(vals), coalesce_linger=0.5, priority_linger=0.5
    )
    pool.lane_of_vote = lambda v: LANE_PRIORITY if lane == "prio" else LANE_BULK
    mem.check_tx(tx)
    m = flow.metrics  # the process's registry: read as differences
    m0 = (m.coalesce_quorum_flushes.value(), m.coalesce_quorum_probed.value())
    flow.start()
    try:
        t0 = time.monotonic()
        pool.check_tx_many(votes)
        assert _wait(lambda: app.tx_count == 1)
        took = time.monotonic() - t0
        stats = flow.pipeline_stats()
    finally:
        flow.stop()
    co = flow._prio_lane if lane == "prio" else flow._coalescer
    assert (co.quorum_flushes, co.linger_flushes, co.full_batches) == (1, 0, 0)
    assert took < 0.5
    if lane == "bulk":
        assert stats["coalesce"]["quorum_flushes"] == 1
        assert stats["coalesce"]["quorum_probed"] == 3
        assert m.coalesce_quorum_flushes.value() - m0[0] == 1
        assert m.coalesce_quorum_probed.value() - m0[1] == 3
        assert m.coalesce_quorum_flushes.name == "txflow_coalesce_quorum_flushes"
        assert m.coalesce_quorum_probed.name == "txflow_coalesce_quorum_probed"
    else:
        assert stats["lanes"]["prio_quorum_flushes"] == 1
        assert stats["coalesce"]["quorum_flushes"] == 0
    assert _cert(store, tx) == _cert(golden, tx)


def test_long_tailed_stake_flushes_at_the_frame_whose_stake_crosses():
    """Stake, not votes: the 7 smallest of 10 long-tailed validators are
    two thirds of the validators and under 2/3 of the stake; with the
    second largest they are still under it; the largest carries the
    crossing. Only that frame ends the hold."""
    powers = sorted(stake_distribution("longtail", 10, seed=3), reverse=True)
    pvs = [MockPV() for _ in powers]
    vals = ValidatorSet([
        Validator.from_pub_key(pv.get_pub_key(), p) for pv, p in zip(pvs, powers)
    ])
    quorum = vals.quorum_power()
    by_power = list(zip(powers, pvs))  # largest first
    frames = [[pv for _, pv in by_power[3:]], [by_power[1][1]], [by_power[0][1]]]
    assert 3 * len(frames[0]) >= 2 * len(pvs)
    assert sum(powers[3:]) < sum(powers[3:]) + powers[1] < quorum
    assert sum(powers[3:]) + powers[1] + powers[0] >= quorum

    tx = b"qf-longtail=1"
    votes = [[sign_vote(pv, tx) for pv in frame] for frame in frames]
    golden = _golden(vals, [tx], [v for frame in votes for v in frame])

    flow, mem, pool, store, app = _holding_engine(vals, _ladder_verifier(vals))
    mem.check_tx(tx)
    flow.start()
    try:
        co = flow._coalescer
        fed = 0
        for frame in votes[:2]:
            pool.check_tx_many(frame)
            fed += len(frame)
            assert _wait(lambda: flow._probe_bulk.probed == fed)
            time.sleep(0.1)  # long enough for a flush the probe got wrong
            assert co.holding and app.tx_count == 0
            assert (co.quorum_flushes, co.linger_flushes) == (0, 0)
        pool.check_tx_many(votes[2])
        assert _wait(lambda: app.tx_count == 1)
    finally:
        flow.stop()
    assert (co.quorum_flushes, co.linger_flushes, co.full_batches) == (1, 0, 0)
    assert _cert(store, tx) == _cert(golden, tx)


def test_a_backlog_that_covers_a_bucket_never_reaches_the_probe():
    """Flood-shaped: 64 txs' votes are in the pool before the engine
    starts, four full 64-vote buckets. Every decide hands out a bucket, so
    the probe reads nothing and the batches are the buckets."""
    pvs, vals = make_pvs(4)
    txs = [b"qf-flood%d=%d" % (i, i) for i in range(64)]
    sizes: list[int] = []
    flow, mem, pool, store, app = _holding_engine(vals, _ladder_verifier(vals, sizes))
    for tx in txs:
        mem.check_tx(tx)
    pool.check_tx_many([sign_vote(pv, tx) for tx in txs for pv in pvs])
    flow.start()
    try:
        assert _wait(lambda: app.tx_count == len(txs), timeout=30.0)
        stats = flow.pipeline_stats()["coalesce"]
    finally:
        flow.stop()
    assert sizes == [64, 64, 64, 64]
    assert stats["full_batches"] == 4
    assert (stats["quorum_flushes"], stats["quorum_probed"], stats["linger_flushes"]) == (0, 0, 0)
