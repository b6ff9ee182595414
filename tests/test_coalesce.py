"""Shape-stable batch coalescing, background warmup, and adaptive depth.

Three invariants from the compile-free hot path work:

1. the coalescer only changes WHEN votes are dispatched, never what is
   decided — certificates stay byte-identical to the scalar golden path,
   including linger-deadline flushes and the cold-shape scalar fallback
   mid-promotion;
2. the shape registry's enumeration is a superset of every shape the
   coalescer can make the verifier emit (so prewarm/background warmup
   covers the hot path: compile_in_run == 0 by construction);
3. the adaptive depth controller steers pipeline_depth from overlap
   signals with bounded, damped movement.
"""

import hashlib
import threading
import time

import numpy as np
import pytest

from test_pipeline import (
    _mixed_stream,
    _wait_quiescent,
    make_engine,
    make_pvs,
    sign_vote,
)
from test_verifier import make_batch, make_valset
from txflow_tpu.engine.adaptive import AdaptiveDepthController
from txflow_tpu.engine.shapes import BackgroundWarmer, ShapeWarmRegistry
from txflow_tpu.engine.txflow import _BatchCoalescer
from txflow_tpu.verifier import (
    DeviceVoteVerifier,
    ScalarVoteVerifier,
)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


# ---- _BatchCoalescer unit behavior ------------------------------------


def test_coalescer_dispatches_full_buckets_only():
    clk = FakeClock()
    co = _BatchCoalescer((8, 32, 128), cap=64, min_batch=4, linger=0.01, clock=clk)
    # cap excludes 128; min_batch excludes nothing else
    assert co.targets == [8, 32]
    # below the smallest bucket: hold (deadline armed, no dispatch)
    assert co.decide(5) == 0
    # backlog covers a bucket: exactly the LARGEST covered bucket drains
    assert co.decide(9) == 8
    assert co.decide(32) == 32
    assert co.decide(70) == 32  # remainder carries to the next decide
    assert co.full_batches == 3
    assert co.linger_flushes == 0


def test_coalescer_linger_deadline_flushes_partial():
    clk = FakeClock()
    co = _BatchCoalescer((8,), cap=64, min_batch=1, linger=0.5, clock=clk)
    assert co.decide(3) == 0  # arms deadline at t+0.5
    clk.t += 0.3
    assert co.decide(3) == 0  # still inside the linger window
    clk.t += 0.3
    assert co.decide(3) == 3  # deadline passed: flush the whole backlog
    assert co.linger_flushes == 1
    # deadline re-arms fresh for the next partial
    assert co.decide(2) == 0
    clk.t += 0.6
    assert co.decide(2) == 2
    assert co.linger_flushes == 2


def test_coalescer_idle_flush_and_wait_budget():
    clk = FakeClock()
    co = _BatchCoalescer((8,), cap=64, min_batch=1, linger=10.0, clock=clk)
    # nothing pending: note_idle is a no-op, wait budget is the poll
    co.note_idle()
    assert co.wait_budget(0.25, 0.05) == 0.25
    assert co.decide(3) == 0
    # deadline armed: the wait is clipped to idle_flush so idleness is
    # detected on that scale, never a full 10 s linger
    assert co.wait_budget(0.25, 0.05) == 0.05
    co.note_idle()  # pool wait timed out with votes pending
    assert co.decide(3) == 3
    assert co.linger_flushes == 1


def test_coalescer_degrades_to_cap_when_no_bucket_fits():
    co = _BatchCoalescer((256, 1024), cap=64, min_batch=1, linger=0.01)
    assert co.targets == [64]
    assert co.decide(64) == 64


# ---- adaptive depth controller ----------------------------------------


def test_adaptive_depth_controller_steers_from_overlap():
    ctrl = AdaptiveDepthController(
        depth=2, min_depth=2, max_depth=4, window=8, cooldown=1
    )

    def window_obs(ratio):
        # feed one full window whose busy/active delta has that ratio
        return ctrl.observe(
            ctrl._last_busy + ratio, ctrl._last_active + 1.0,
            ctrl._last_steps + ctrl.window,
        )

    # sub-window feeds never move the depth
    assert ctrl.observe(0.1, 1.0, ctrl.window - 1) == 2
    # low overlap: the device idled while the engine worked -> grow
    assert window_obs(0.5) == 3
    assert ctrl.changes == 1
    # cooldown window: even a terrible ratio holds the new depth
    assert window_obs(0.5) == 3
    # cooldown over: grow again, then clamp at max
    assert window_obs(0.5) == 4
    assert window_obs(0.5) == 4  # cooldown
    assert window_obs(0.5) == 4  # at max_depth: no further growth
    # saturated device: probe down (damped), never below the floor
    for _ in range(10):
        window_obs(1.0)
    assert ctrl.depth == ctrl.min_depth == 2
    assert ctrl.changes >= 3
    assert ctrl.stats()["last_window_ratio"] == 1.0
    # mid-band ratio: hold
    held = window_obs(0.9)
    assert held == 2 and ctrl.depth == 2


def test_adaptive_depth_engine_wiring():
    """adaptive_depth=True wires a controller into the pipelined loop:
    the engine still commits correctly, pipeline_stats reports the
    controller, and synthetic overlap signals move the depth the fill
    stage honors (_target_depth) — the ROADMAP static-depth item."""
    pvs, vals = make_pvs(4)
    flow, mempool, votepool, store, app = make_engine(
        vals,
        use_device=False,
        coalesce=False,
        adaptive_depth=True,
        pipeline_depth=2,
        pipeline_depth_max=6,
        min_batch=1,
        max_batch=8,
    )
    txs = [b"ad%d=v" % i for i in range(12)]
    for tx in txs:
        mempool.check_tx(tx)
    flow.start()
    try:
        for tx in txs:
            for pv in pvs[:3]:
                votepool.check_tx(sign_vote(pv, tx))
        assert _wait_quiescent(flow, votepool)
    finally:
        flow.stop()
    assert app.tx_count == len(txs)

    ctrl = flow._depth_ctrl
    assert ctrl is not None
    stats = flow.pipeline_stats()
    assert stats["adaptive_depth"]["depth"] == ctrl.depth == flow._target_depth()
    # synthetic idle-device windows grow the live depth...
    d0 = ctrl.depth
    grown = ctrl.observe(
        ctrl._last_busy + 0.1, ctrl._last_active + 1.0,
        ctrl._last_steps + ctrl.window,
    )
    assert grown == min(d0 + 1, ctrl.max_depth)
    assert flow._target_depth() == grown
    assert flow.pipeline_stats()["depth"] == grown
    # ...and saturated windows walk it back to the floor
    for _ in range(20):
        ctrl.observe(
            ctrl._last_busy + 1.0, ctrl._last_active + 1.0,
            ctrl._last_steps + ctrl.window,
        )
    assert ctrl.depth == ctrl.min_depth
    assert flow._target_depth() == ctrl.min_depth
    assert ctrl.changes >= 2


# ---- coalescing parity (satellite: the golden-path guarantee) ---------


class FakeWarmGate:
    """Stands in for ShapeWarmRegistry in the engine's cold-shape gate:
    starts cold (every batch demoted to the scalar fallback), promotes
    when the test flips ``warm`` — exercising the fallback->device
    promotion boundary without a device."""

    def __init__(self):
        self.warm = False
        self.warmed: set = set()

    def is_batch_warm(self, n, n_slots=1):
        return self.warm

    def enumerate_shapes(self, n=1, full=True):
        return [("fused", 8, 8)]


@pytest.mark.parametrize("tail_votes", [4, 5], ids=["tail_short", "tail_quorum"])
@pytest.mark.parametrize("seed", [41, 97])
def test_coalescing_parity_with_cold_fallback(seed, tail_votes):
    """Randomized stream through the coalescing engine — including
    linger-deadline and quorum flushes and the cold-shape scalar fallback
    flipping to the primary verifier MID-RUN — produces certificates
    byte-identical to the scalar try_add_vote golden path."""
    pvs, vals = make_pvs(7)  # total 70, quorum 47 -> 5 votes needed
    txs = [b"co%d-%d=%d" % (seed, i, i) for i in range(16)]
    stream = _mixed_stream(pvs, txs, seed)

    # sub-bucket tail: fed in one frame after the main stream drains, so
    # these votes can never join a full bucket. One vote short of the
    # quorum (stake 40 < 47: pending in a vote set, no commit) they decide
    # nothing and MUST leave via the linger deadline; with the fifth
    # (50 >= 47) they complete the quorum and leave at once
    tail_tx = b"co%d-tail=1" % seed
    tail = [sign_vote(pv, tail_tx) for pv in pvs[:tail_votes]]

    # scalar golden path
    flow_s, mem_s, _, store_s, app_s = make_engine(vals, use_device=False)
    for tx in txs + [tail_tx]:
        mem_s.check_tx(tx)
    for v in stream + tail:
        flow_s.try_add_vote(v.copy())

    # coalescing engine: duck-typed bucket ladder on a scalar verifier
    # (the coalescer activates off verifier.buckets, device not needed)
    primary = ScalarVoteVerifier(vals)
    primary.buckets = (8, 32)
    primary_calls = {"n": 0}
    orig_vt = primary.verify_and_tally

    def spy(*a, **kw):
        primary_calls["n"] += 1
        return orig_vt(*a, **kw)

    primary.verify_and_tally = spy
    flow_p, mem_p, pool_p, store_p, app_p = make_engine(
        vals,
        use_device=False,
        verifier=primary,
        max_batch=32,
        min_batch=4,
        pipeline_depth=3,
        coalesce=True,
        coalesce_linger=0.02,
    )
    # cold-shape gate: batches demote to the fallback until promotion
    gate = FakeWarmGate()
    flow_p._warm_gate = gate
    flow_p._cold_fallback = ScalarVoteVerifier(vals)
    for tx in txs + [tail_tx]:
        mem_p.check_tx(tx)
    flow_p.start()
    try:
        assert flow_p._coalescer is not None, "bucket ladder not picked up"
        half = len(stream) // 2
        for v in stream[:half]:
            try:
                pool_p.check_tx(v)
            except Exception:
                pass  # stranger/dup — the scalar path saw the vote anyway
        deadline = time.monotonic() + 10.0
        while flow_p._cold_fallback_votes == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert flow_p._cold_fallback_votes > 0, "no batch took the fallback"
        gate.warm = True  # background warmer finished: promote
        for v in stream[half:]:
            try:
                pool_p.check_tx(v)
            except Exception:
                pass
        assert _wait_quiescent(flow_p, pool_p), "coalescing engine never drained"
        co = flow_p._coalescer
        before = (co.linger_flushes, co.quorum_flushes)
        pool_p.check_tx_many(tail)
        assert _wait_quiescent(flow_p, pool_p), "tail dribble never flushed"
    finally:
        flow_p.stop()

    # the dispatch-shaping actually happened: canonical full buckets, the
    # sub-bucket tail leaving by the deadline or by its quorum, then
    # post-promotion batches on the primary verifier
    assert co.full_batches > 0
    if tail_votes == 4:
        assert co.linger_flushes > before[0]
        assert co.quorum_flushes == before[1]
    else:
        assert co.quorum_flushes > before[1]
    assert primary_calls["n"] > 0, "no batch promoted to the primary verifier"
    stats = flow_p.pipeline_stats()
    assert stats["coalesce"]["enabled"]
    assert stats["coalesce"]["cold_fallback_votes"] == flow_p._cold_fallback_votes
    assert stats["warmup"]["total_shapes"] == 1

    # decisions byte-identical to the golden path
    assert app_p.tx_count == app_s.tx_count
    assert app_p.state == app_s.state
    assert app_p.digest == app_s.digest  # commit ORDER identical
    for tx in txs + [tail_tx]:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        cs = store_s.load_tx_commit(tx_hash)
        cp = store_p.load_tx_commit(tx_hash)
        assert (cs is None) == (cp is None)
        if cs is not None:
            assert [
                (c.validator_address, c.signature) for c in cs.commits
            ] == [(c.validator_address, c.signature) for c in cp.commits]
    for tx_hash, vs in flow_s.vote_sets.items():
        assert flow_p.vote_sets[tx_hash].stake() == vs.stake()


def test_coalescer_inactive_without_bucket_ladder():
    """A plain scalar verifier exposes no buckets: coalesce=True must
    leave the legacy min_batch/_form_batch path untouched."""
    pvs, vals = make_pvs(4)
    flow, mempool, votepool, _, app = make_engine(
        vals, use_device=False, coalesce=True, min_batch=1
    )
    tx = b"nocoal=1"
    mempool.check_tx(tx)
    flow.start()
    try:
        for pv in pvs[:3]:
            votepool.check_tx(sign_vote(pv, tx))
        assert _wait_quiescent(flow, votepool)
    finally:
        flow.stop()
    assert flow._coalescer is None
    assert app.tx_count == 1
    assert flow.pipeline_stats()["coalesce"]["enabled"] is False


# ---- shape registry covers every coalescer-emittable shape ------------


def test_registry_enumerates_every_coalescer_shape():
    """Tier-1 guard for compile_in_run == 0: for EVERY batch size the
    coalescer can emit (bucket sizes, linger flushes of any smaller
    size, retry-inflated sizes up to the cap), the shapes the verifier
    can dispatch are inside the prewarm enumeration."""
    vals, _seeds = make_valset(4)
    # the slot bucket tracks n_slots; warmup's contract covers the
    # floor-slot and full-width combos the engine dispatches
    dev = DeviceVoteVerifier(vals, buckets=(64, 256))
    reg = ShapeWarmRegistry(dev)
    universe = set(reg.enumerate_shapes(full=True))
    sizes = sorted(
        {1, 2, dev.max_batch}
        | {b for b in dev.buckets}
        | {b - 1 for b in dev.buckets}
        | {b + 1 for b in dev.buckets if b + 1 <= dev.max_batch}
    )
    for n in sizes:
        for n_slots in (1, max(1, n // 2), n):
            got = set(reg.shapes_for_batch(n, n_slots))
            assert got, f"no shapes predicted for n={n}"
            assert got <= universe, (n, n_slots, got - universe)

    # scalar verifier: no compiled shapes, every batch warm by definition
    reg_s = ShapeWarmRegistry(ScalarVoteVerifier(vals))
    assert reg_s.shapes_for_batch(100) == []
    assert reg_s.is_batch_warm(100)


def test_background_warmer_promotes_registry():
    """BackgroundWarmer compiles the enumeration off the hot path: the
    registry flips from cold to warm without prewarm, and nothing the
    warmer compiled reads as an in-run compile."""
    vals, _seeds = make_valset(4)
    dev = DeviceVoteVerifier(vals, buckets=(64,))
    reg = ShapeWarmRegistry(dev)
    assert not reg.is_batch_warm(5)
    warmer = BackgroundWarmer(reg, full=True)
    warmer._run()  # synchronous: the thread body, minus the thread
    assert warmer.compiled >= 1 and warmer.failed == 0
    assert reg.is_batch_warm(5)
    assert reg.is_batch_warm(64)
    assert reg.cold_shapes() == []  # warmer compiles are warm, not cold
    # a warmed registry stays consistent with a real dispatch
    msgs, sigs, vidx, slot = make_batch(vals, _seeds, n_txs=2)
    dev.verify_and_tally(msgs, sigs, vidx, slot, 2)
    assert reg.cold_shapes() == []

    # scalar verifier: start() is a no-op, no thread ever exists
    w2 = BackgroundWarmer(ShapeWarmRegistry(ScalarVoteVerifier(vals)))
    w2.start()
    assert w2._thread is None and not w2.done()


# ---- LocalNet guard (satellite: partial hosting + consensus) ----------


def test_localnet_rejects_consensus_with_partial_hosting():
    """enable_consensus with a hosted subset silently hangs at round 0
    (the missing validators never prevote): must fail fast instead."""
    from txflow_tpu.node import LocalNet

    with pytest.raises(ValueError, match="hosting all"):
        LocalNet(4, n_nodes=2, enable_consensus=True)
    # the non-consensus subset config stays legal (bench 16/64-validator
    # sweeps host 4 nodes); no start() — construction is the assertion
    net = LocalNet(4, n_nodes=2, enable_consensus=False)
    assert len(net.nodes) == 2
