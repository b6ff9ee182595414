"""Chaos suite: the fault-injection subsystem (txflow_tpu/faults/) driven
against live LocalNets.

Each fault class gets at least one fast deterministic scenario in tier-1;
long soaks are marked ``slow``. Every network scenario asserts the two
paper-level properties:

- SAFETY: no conflicting commit certificates — on every node, every
  committed tx's certificate is built from distinct in-set validators
  whose signatures verify, none byzantine, summing past 2/3 stake;
- LIVENESS: every honest client tx commits on every node.
"""

import hashlib
import itertools
import time

import conftest
import numpy as np
import pytest

from txflow_tpu.crypto import ed25519 as host_ed
from txflow_tpu.faults import (
    ChaosRouter,
    CrashDrill,
    FaultPlan,
    FaultSpec,
    FlakyVerifier,
    InjectedDeviceError,
    byzantine,
)
from txflow_tpu.faults.plan import DELIVER, GOSSIP_CHANNELS
from txflow_tpu.node.localnet import LocalNet
from txflow_tpu.p2p.base import CHANNEL_CONSENSUS_STATE, CHANNEL_TXVOTE
from txflow_tpu.pool.evidence import EvidencePool
from txflow_tpu.types import MockPV, TxVote, Validator, ValidatorSet
from txflow_tpu.types.tx_vote import canonical_sign_bytes
from txflow_tpu.verifier import ResilientVoteVerifier, ScalarVoteVerifier

CHAIN_ID = "txflow-localnet"  # LocalNet default


def wait_until(pred, timeout=20.0, poll=0.01):
    deadline = time.monotonic() + timeout * conftest.WAIT_FACTOR
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return False


def _mkpvs(n, tag=b"chaos-val"):
    return [MockPV(hashlib.sha256(tag + b"%d" % i).digest()) for i in range(n)]


def assert_certificate_safety(net, txs, byz_addrs=frozenset()):
    """No conflicting certificates: every node's certificate for every tx
    is distinct, in-set, non-byzantine validators with verifying
    signatures whose stake clears the >2/3 quorum."""
    total = net.val_set.total_voting_power()
    for node in net.nodes:
        for tx in txs:
            h = hashlib.sha256(tx).hexdigest().upper()
            votes = node.tx_store.load_tx_votes(h)
            assert votes, f"{node.node_id}: no certificate for {h[:12]}"
            addrs = [v.validator_address for v in votes]
            assert len(addrs) == len(set(addrs)), (
                f"{node.node_id}: duplicate validator in certificate {h[:12]}"
            )
            stake = 0
            for v in votes:
                assert v.validator_address not in byz_addrs, (
                    f"{node.node_id}: byzantine validator certified {h[:12]}"
                )
                _, val = net.val_set.get_by_address(v.validator_address)
                assert val is not None, f"{node.node_id}: out-of-set validator"
                assert v.verify(net.chain_id, val.pub_key) is None, (
                    f"{node.node_id}: unverifiable signature in cert {h[:12]}"
                )
                stake += val.voting_power
            assert stake * 3 > total * 2, (
                f"{node.node_id}: certificate {h[:12]} below quorum "
                f"({stake}/{total})"
            )


# ------------------------------------------------------ FaultPlan (pure)


def test_fault_plan_same_seed_same_trace():
    """Same seed => identical per-link fault trace, independent of how
    calls from different links interleave."""
    spec = FaultSpec(seed=11, drop=0.2, duplicate=0.1, delay=0.2)
    links = [("n0", "n1"), ("n1", "n0"), ("n0", "n2"), ("n2", "n1")]

    def drive(plan, order):
        for i in range(200):
            for src, dst in order:
                plan.decide(src, dst, CHANNEL_TXVOTE)

    a, b = FaultPlan(spec), FaultPlan(spec)
    drive(a, links)
    drive(b, list(reversed(links)))  # different cross-link interleaving
    assert a.trace, "a 0.5 total fault rate over 800 draws must fire"
    for src, dst in links:
        assert a.link_trace(src, dst) == b.link_trace(src, dst)
    # a different seed yields a different pattern
    c = FaultPlan(FaultSpec(seed=12, drop=0.2, duplicate=0.1, delay=0.2))
    drive(c, links)
    assert c.link_trace("n0", "n1") != a.link_trace("n0", "n1")


def test_fault_plan_scope_does_not_consume_randomness():
    """Out-of-scope (consensus) traffic interleaved into a link must not
    shift the gossip-channel decision stream."""
    spec = FaultSpec(seed=3, drop=0.3, delay=0.3)
    assert CHANNEL_CONSENSUS_STATE not in GOSSIP_CHANNELS
    a, b = FaultPlan(spec), FaultPlan(spec)
    for i in range(100):
        a.decide("x", "y", CHANNEL_TXVOTE)
        kind, delay = b.decide("x", "y", CHANNEL_CONSENSUS_STATE)
        assert (kind, delay) == (DELIVER, 0.0)
        b.decide("x", "y", CHANNEL_TXVOTE)
    assert a.link_trace("x", "y") == b.link_trace("x", "y")


def test_fault_spec_validates():
    with pytest.raises(ValueError):
        FaultSpec(drop=0.7, delay=0.6)  # probabilities sum past 1
    with pytest.raises(ValueError):
        FaultSpec(delay_min=0.2, delay_max=0.1)


# ------------------------------------------------- lossy links (LocalNet)


def test_chaos_lossy_links_all_commit():
    """drop + duplicate + delay on every gossip link: anti-entropy
    regossip restores liveness; certificates stay clean."""
    spec = FaultSpec(
        seed=7, drop=0.15, duplicate=0.1, delay=0.15,
        delay_min=0.001, delay_max=0.02,
    )
    net = LocalNet(4, use_device_verifier=False, fault_plan=spec)
    txs = [b"lossy-%d=v" % i for i in range(8)]
    try:
        net.start()
        for i, tx in enumerate(txs):
            net.broadcast_tx(tx, node_index=i % 4)
        assert net.wait_all_committed(txs, timeout=60), (
            f"liveness under loss: stats={dict(net.chaos.stats)}"
        )
        assert_certificate_safety(net, txs)
        # the plan actually fired each fault class
        assert net.chaos.stats["drop"] > 0
        assert net.chaos.stats["duplicate"] > 0
        assert net.chaos.stats["delay"] > 0
    finally:
        net.stop()


def test_chaos_partition_halts_then_heals():
    """A 2/2 partition starves quorum (neither side has 2/3 stake); after
    heal(), regossip carries the backlog and every node commits."""
    net = LocalNet(4, use_device_verifier=False, fault_plan=FaultSpec(seed=0))
    pre = b"pre-partition=v"
    cut = b"cut-partition=v"
    try:
        net.start()
        net.broadcast_tx(pre)
        assert net.wait_all_committed([pre], timeout=30)

        net.chaos.partition({"node0", "node1"})  # node2/node3: implicit group
        net.broadcast_tx(cut)
        h = hashlib.sha256(cut).hexdigest().upper()
        time.sleep(1.2)
        assert not any(n.tx_store.has_tx(h) for n in net.nodes), (
            "a 2-of-4 side must not reach the 2/3 quorum"
        )
        assert net.chaos.stats["partitioned"] > 0

        net.chaos.heal()
        assert net.wait_all_committed([cut], timeout=60), (
            "liveness must resume after heal"
        )
        assert_certificate_safety(net, [pre, cut])
    finally:
        net.stop()


# ---------------------------------------------------- byzantine validators


def test_byzantine_vote_flood_excluded_from_certificates():
    """One validator floods equivocating / garbage / wrong-chain / forged /
    stale votes: commits keep flowing, and no certificate anywhere
    contains an unverifiable vote or counts a validator twice."""
    pvs = _mkpvs(4)
    net = LocalNet(4, use_device_verifier=False, priv_vals=pvs)
    gen = byzantine.ByzantineVoteGen(pvs[0], CHAIN_ID, seed=5)
    txs = [b"byz-%d=v" % i for i in range(4)]
    try:
        net.start()
        for tx in txs:
            net.broadcast_tx(tx)
        # hostile flood into node1's pool (gossip spreads it from there)
        pool = net.nodes[1].tx_vote_pool
        a, b = gen.equivocating_pair(txs[0])
        pool.check_tx(a)
        pool.check_tx(b)
        pool.check_tx(gen.garbage_signature_vote(txs[1]))
        pool.check_tx(gen.wrong_chain_vote(txs[2]))
        pool.check_tx(gen.forged_address_vote(txs[3], pvs[1].get_address()))
        pool.check_tx(gen.stale_vote(txs[0], height=0))
        assert net.wait_all_committed(txs, timeout=60)
        # pvs[0] is equivocating but its signatures are VALID: it may
        # legitimately appear in certificates — at most once per tx, with
        # a verifying signature (assert_certificate_safety checks both)
        assert_certificate_safety(net, txs)
    finally:
        net.stop()


def test_byzantine_garbage_signer_liveness():
    """A validator whose every signature fails verification (withheld
    stake, effectively): 3/4 honest stake still commits everything and
    the byzantine address never enters a certificate."""
    pvs = _mkpvs(4, tag=b"garbage-val")
    pvs[0].break_tx_vote_signing = True  # signs for the wrong chain id
    net = LocalNet(4, use_device_verifier=False, priv_vals=pvs)
    txs = [b"garbage-%d=v" % i for i in range(4)]
    try:
        net.start()
        for i, tx in enumerate(txs):
            net.broadcast_tx(tx, node_index=i % 4)
        assert net.wait_all_committed(txs, timeout=60), (
            "3 honest of 4 must keep committing"
        )
        assert_certificate_safety(
            net, txs, byz_addrs={pvs[0].get_address()}
        )
    finally:
        net.stop()


def test_block_equivocation_evidence_admitted_and_forged_rejected():
    """Block-path equivocation goes through types/evidence.py: a validly
    double-signed pair is admitted to the pool; a forged accusation (bad
    second signature) is rejected."""
    pv = MockPV(hashlib.sha256(b"equivocator").digest())
    vs = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), 10)])
    pool = EvidencePool("ev-chain", lambda: vs)

    ev = byzantine.equivocating_block_votes(pv, "ev-chain", height=5)
    added, err = pool.add(ev)
    assert added and err is None
    assert pool.has(ev) and len(pool.pending()) == 1
    # duplicate submission: known, not an error
    added, err = pool.add(ev)
    assert not added and err is None

    forged = byzantine.forged_block_vote_evidence(pv, "ev-chain", height=6)
    added, err = pool.add(forged)
    assert not added and err is not None
    assert len(pool.pending()) == 1

    # an out-of-set accuser is rejected too
    stranger = MockPV(hashlib.sha256(b"stranger").digest())
    added, err = pool.add(
        byzantine.equivocating_block_votes(stranger, "ev-chain", height=7)
    )
    assert not added and err is not None


# ------------------------------------------------------ crash-restart drill


def test_crash_drill_restart_replays_exactly_once(tmp_path):
    """Kill the drill node right after a commit persists; the restarted
    node (fresh app) replays every commit exactly once, in order."""
    import collections

    from txflow_tpu.abci import KVStoreApplication

    class CountingKVStore(KVStoreApplication):
        def __init__(self):
            super().__init__()
            self.delivered = collections.Counter()

        def deliver_tx(self, tx):
            self.delivered[bytes(tx)] += 1
            return super().deliver_tx(tx)

    drill = CrashDrill(tmp_path)
    try:
        drill.start()
        pre = [b"drill-%d=v" % i for i in range(3)]
        for tx in pre:
            drill.submit(tx)
        assert drill.wait_committed(pre)
        order_before = drill.committed_order()

        victim = b"drill-victim=v"
        from txflow_tpu.utils import failpoints

        failpoints.arm("txflow-after-commit")
        drill.submit(victim)
        drill.crash(failpoint="txflow-after-commit")

        app2 = CountingKVStore()
        drill.restart(app2)
        assert drill.restarts == 1
        assert drill.wait_committed(pre + [victim])
        for tx in pre + [victim]:
            assert app2.delivered[tx] == 1, (
                f"{tx} delivered {app2.delivered[tx]}x"
            )
        # replay converges: pre-crash order is a prefix of the new order
        order_after = drill.committed_order()
        assert order_after[: len(order_before)] == order_before
        # the restarted node still makes progress
        fresh = b"drill-fresh=v"
        drill.submit(fresh)
        assert drill.wait_committed([fresh])
        # wait_committed sees the persisted certificate, which lands
        # ahead of the async committer's app delivery — give the
        # delivery a bounded window before asserting exactly-once
        deadline = time.monotonic() + 10.0
        while app2.delivered[fresh] == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert app2.delivered[fresh] == 1
    finally:
        drill.stop()


# --------------------------------------------- verifier graceful degradation


def _degradation_rig():
    """A 4-validator batch plus a golden result to compare every path to."""
    pvs = _mkpvs(4, tag=b"deg-val")
    vs = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), 10) for pv in pvs])
    by_addr = {pv.get_address(): pv for pv in pvs}
    msgs, sigs, vidx, slot = [], [], [], []
    for t in range(2):
        tx_hash = hashlib.sha256(b"deg-tx%d" % t).hexdigest().upper()
        for vi, val in enumerate(vs.validators):
            v = TxVote(
                height=1,
                tx_hash=tx_hash,
                tx_key=hashlib.sha256(b"deg-tx%d" % t).digest(),
                timestamp_ns=1_700_000_000_000_000_000 + t,
                validator_address=val.address,
            )
            by_addr[val.address].sign_tx_vote(CHAIN_ID, v)
            msgs.append(
                canonical_sign_bytes(CHAIN_ID, 1, tx_hash, v.timestamp_ns)
            )
            sigs.append(v.signature)
            vidx.append(vi)
            slot.append(t)
    batch = (msgs, sigs, np.array(vidx), np.array(slot), 2)
    golden = ScalarVoteVerifier(vs).verify_and_tally(*batch)
    return vs, batch, golden


def _assert_same(result, golden):
    np.testing.assert_array_equal(result.valid, golden.valid)
    np.testing.assert_array_equal(result.stake, golden.stake)
    np.testing.assert_array_equal(result.maj23, golden.maj23)


def test_resilient_verifier_retries_demotes_and_repromotes():
    """The full policy, deterministically: bounded retry with exponential
    backoff -> demotion to the CPU fallback -> probe after the interval
    -> re-promotion. Decisions are bit-identical on every path."""
    vs, batch, golden = _degradation_rig()
    flaky = FlakyVerifier(ScalarVoteVerifier(vs))
    sleeps, now, transitions = [], [0.0], []
    r = ResilientVoteVerifier(
        flaky,
        fallback=ScalarVoteVerifier(vs),
        max_attempts=3,
        backoff_base=0.01,
        backoff_max=0.04,
        probe_interval=5.0,
        sleep=sleeps.append,
        clock=lambda: now[0],
    )
    r.on_state_change = transitions.append

    _assert_same(r.verify_and_tally(*batch), golden)  # healthy: device path
    assert flaky.calls == 1 and r.fallback_calls == 0 and r.device_healthy

    flaky.failing = True
    _assert_same(r.verify_and_tally(*batch), golden)  # served by fallback
    assert sleeps == [0.01, 0.02], "exponential backoff between attempts"
    assert r.device_failures == 3 and r.demotions == 1
    assert not r.device_healthy and r.fallback_calls == 1
    assert isinstance(r.last_error, InjectedDeviceError)
    assert transitions == [False]

    # demoted + probe not due: the device is not even tried
    calls = flaky.calls
    _assert_same(r.verify_and_tally(*batch), golden)
    assert flaky.calls == calls and r.fallback_calls == 2

    # probe due, device still down: one probe burst, stays demoted
    now[0] = 6.0
    _assert_same(r.verify_and_tally(*batch), golden)
    assert flaky.calls == calls + 3 and r.fallback_calls == 3
    assert r.demotions == 1, "a failed probe is not a second demotion"

    # next caller inside the re-armed interval skips the device again
    now[0] = 7.0
    calls = flaky.calls
    _assert_same(r.verify_and_tally(*batch), golden)
    assert flaky.calls == calls

    # device recovers; the next probe re-promotes
    flaky.failing = False
    now[0] = 20.0
    _assert_same(r.verify_and_tally(*batch), golden)
    assert r.repromotions == 1 and r.device_healthy
    assert transitions == [False, True]
    fallback_calls = r.fallback_calls
    _assert_same(r.verify_and_tally(*batch), golden)  # back on the device
    assert r.fallback_calls == fallback_calls


def test_localnet_commits_through_device_outage_and_recovery():
    """End-to-end degradation: every node's engine shares a resilient
    verifier whose device is down from the start — commits flow on the
    CPU fallback; when the device heals, a probe re-promotes it and
    later commits ride the device path again."""
    pvs = _mkpvs(4, tag=b"outage-val")
    vs = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), 10) for pv in pvs])
    flaky = FlakyVerifier(ScalarVoteVerifier(vs))
    flaky.failing = True
    resilient = ResilientVoteVerifier(
        flaky,
        fallback=ScalarVoteVerifier(vs),
        max_attempts=2,
        backoff_base=0.001,
        probe_interval=0.2,
    )
    net = LocalNet(
        4, use_device_verifier=False, priv_vals=pvs, verifier=resilient
    )
    try:
        net.start()
        down = [b"outage-%d=v" % i for i in range(3)]
        for tx in down:
            net.broadcast_tx(tx)
        assert net.wait_all_committed(down, timeout=60 * conftest.WAIT_FACTOR), (
            "fallback must keep commits flowing while the device is down"
        )
        assert not resilient.device_healthy and resilient.demotions == 1
        assert resilient.fallback_calls > 0
        assert_certificate_safety(net, down)

        flaky.failing = False  # device recovers
        up = [b"recovered-%d=v" % i for i in range(3)]
        for tx in up:
            net.broadcast_tx(tx)
        assert net.wait_all_committed(up, timeout=60 * conftest.WAIT_FACTOR)
        # only a verify call can probe: the three txs above may all have
        # committed on the fallback INSIDE the probe interval that the
        # last failed probe re-armed, after which an idle net never asks
        # the device again — keep traffic flowing until a probe is due
        more = itertools.count()

        def healed():
            if not resilient.device_healthy:
                net.broadcast_tx(b"probe-%d=v" % next(more))
            return resilient.device_healthy

        assert wait_until(healed, timeout=20, poll=0.05), (
            "a probe within probe_interval must re-promote the device"
        )
        assert resilient.repromotions == 1
        assert_certificate_safety(net, up)
    finally:
        net.stop()


# --------------------------------------------------------------- slow soaks


@pytest.mark.slow
def test_chaos_soak_loss_partition_byzantine():
    """Everything at once, longer: lossy links + a partition cycle + a
    garbage-signing validator + an equivocation flood, 32 txs."""
    pvs = _mkpvs(4, tag=b"soak-val")
    pvs[3].break_tx_vote_signing = True
    spec = FaultSpec(
        seed=99, drop=0.2, duplicate=0.15, delay=0.2,
        delay_min=0.001, delay_max=0.05,
    )
    net = LocalNet(4, use_device_verifier=False, priv_vals=pvs, fault_plan=spec)
    gen = byzantine.ByzantineVoteGen(pvs[0], CHAIN_ID, seed=99)
    txs = [b"soak-%d=v" % i for i in range(32)]
    try:
        net.start()
        for i, tx in enumerate(txs[:16]):
            net.broadcast_tx(tx, node_index=i % 4)
            if i % 4 == 0:
                a, b = gen.equivocating_pair(tx)
                net.nodes[1].tx_vote_pool.check_tx(a)
                net.nodes[1].tx_vote_pool.check_tx(b)
        assert net.wait_all_committed(txs[:16], timeout=120)

        net.chaos.partition({"node0"}, {"node1"})  # 1/1/2: no quorum anywhere
        time.sleep(1.0)
        net.chaos.heal()

        for i, tx in enumerate(txs[16:]):
            net.broadcast_tx(tx, node_index=i % 4)
        assert net.wait_all_committed(txs, timeout=120), (
            f"soak liveness: stats={dict(net.chaos.stats)}"
        )
        assert_certificate_safety(net, txs, byz_addrs={pvs[3].get_address()})
    finally:
        net.stop()


@pytest.mark.slow
def test_chaos_soak_seed_replay_matches():
    """Same seed, same workload => the same per-link fault trace from a
    live net (plan determinism holds under real thread interleaving)."""
    def run(seed):
        spec = FaultSpec(seed=seed, drop=0.1, duplicate=0.1, delay=0.1)
        net = LocalNet(4, use_device_verifier=False, fault_plan=spec)
        txs = [b"replay-%d=v" % i for i in range(8)]
        try:
            net.start()
            for i, tx in enumerate(txs):
                net.broadcast_tx(tx, node_index=i % 4)
            assert net.wait_all_committed(txs, timeout=60)
        finally:
            net.stop()
        return net.chaos.plan

    p1, p2 = run(4242), run(4242)
    # the nets are concurrent systems: message COUNTS per link can differ
    # between runs (regossip timing), so compare the common prefix of
    # each link's decision stream — determinism means the streams agree
    # wherever both runs drew them
    links = {(s, d) for (s, d, _, _, _) in p1.trace} | {
        (s, d) for (s, d, _, _, _) in p2.trace
    }
    assert links, "chaos must have fired"
    for src, dst in links:
        drawn = min(
            p1._counts.get((src, dst), 0), p2._counts.get((src, dst), 0)
        )
        assert drawn > 0
        t1 = [e for e in p1.link_trace(src, dst) if e[0] < drawn]
        t2 = [e for e in p2.link_trace(src, dst) if e[0] < drawn]
        assert t1 == t2
