"""Accountable vote gossip (health/byzantine.py + reactor pre-checks +
engine verdict attribution): a Byzantine vote flood is struck, quarantined
at the front door, and priced out of the device — while honest traffic
commits with zero loss and certificates stay byte-identical to the scalar
golden path.

Layers under test:
- ByzantineLedger unit behavior: breaker window/decay, replay opt-in,
  origin attribution, scoreboard charging, sync-strike unification;
- TxVotePool origin bookkeeping (both ingest twins) + add_sender codes;
- engine _route_result -> on_invalid_votes -> ledger strikes;
- reactor O(1) pre-checks (unknown validator / stale height / replay)
  with per-peer accounting, deterministic via crafted frames;
- the tier-1 LocalNet drill: 1-of-4 Byzantine validator + 1 malicious
  non-validator peer, all honest txs commit, every adversary struck AND
  quarantined, post-quarantine device waste bounded (< 5% invalid);
- the equivocator: fast-path stake counted once, block-path evidence
  slashed everywhere (PR 7 bridge), post-slash votes pre-dropped;
- the selective withholder: liveness holds, withheld txs certify
  without the withholder's key.
"""

import hashlib
import time

from txflow_tpu.abci import KVStoreApplication
from txflow_tpu.epoch import EpochConfig
from txflow_tpu.faults import (
    ByzantineVoteGen,
    IdenticalVoteReplayer,
    SelectiveWithholder,
    SigGarbageFlooder,
    StaleVoteSpammer,
    TxVoteEquivocator,
)
from txflow_tpu.faults.byzantine import _encode_vote_frame
from txflow_tpu.health.byzantine import (
    DROP_QUARANTINED,
    DROP_REPLAYED_SIG,
    DROP_STALE_HEIGHT,
    DROP_UNKNOWN_VALIDATOR,
    ByzantineConfig,
    ByzantineLedger,
)
from txflow_tpu.health.config import HealthConfig
from txflow_tpu.node.localnet import LocalNet
from txflow_tpu.node.node import Node, NodeConfig
from txflow_tpu.p2p import connect_switches
from txflow_tpu.p2p.base import CHANNEL_TXVOTE
from txflow_tpu.pool import TxVotePool
from txflow_tpu.pool.mempool import TxInfo
from txflow_tpu.types import MockPV
from txflow_tpu.utils.config import MempoolConfig
from txflow_tpu.utils.config import test_config as make_test_config

from test_engine import make_engine, make_pvs, sign_vote


def wait_until(pred, timeout=30.0, poll=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return pred()


class FakeScoreboard:
    def __init__(self):
        self.calls = []  # (node_id, points)

    def punish(self, node_id, points, now=None):
        self.calls.append((node_id, points))

    def total(self, node_id):
        return sum(p for n, p in self.calls if n == node_id)


# -- ByzantineLedger units -------------------------------------------------


def test_ledger_breaker_trips_on_bad_rate_and_expires():
    led = ByzantineLedger(
        ByzantineConfig(min_samples=8, max_bad_rate=0.5, quarantine_secs=10.0)
    )
    # 4 kept + 4 unknown-validator drops = 8 judged events, half bad
    led.note_frame("p", 4, {DROP_UNKNOWN_VALIDATOR: 4}, now=0.0)
    assert led.quarantined("p", now=0.5)
    assert led.quarantined("p", now=9.9)
    assert not led.quarantined("p", now=10.1)  # sentence served
    snap = led.snapshot(now=1.0)
    assert snap["quarantines"] == 1
    assert snap["strikes"] >= 1
    assert snap["pre_verify_drops"] == 4
    assert snap["quarantined_peers"] == ["p"]
    rec = snap["peers"]["p"]
    assert rec["relayed"] == 4 and rec["quarantined"]
    assert rec["drops"] == {DROP_UNKNOWN_VALIDATOR: 4}


def test_ledger_below_min_samples_never_trips():
    led = ByzantineLedger(ByzantineConfig(min_samples=32, max_bad_rate=0.5))
    # 100% bad rate but only 8 samples: the breaker must hold fire
    led.note_frame("p", 0, {DROP_STALE_HEIGHT: 8}, now=0.0)
    assert not led.quarantined("p", now=0.1)


def test_ledger_window_decays_ratio_preserving():
    led = ByzantineLedger(ByzantineConfig(window=8, min_samples=100))
    led.note_frame("p", 8, now=0.0)  # hits window -> halves
    rec = led._peers["p"]
    assert rec.win_events == 4 and rec.win_bad == 0
    led.note_frame("p", 0, {DROP_UNKNOWN_VALIDATOR: 4}, now=0.1)
    rec = led._peers["p"]
    # 8 events (4 old good + 4 new bad) halved to 4 events / 2 bad:
    # the bad fraction survives the decay, the raw magnitude does not
    assert rec.win_events == 4 and rec.win_bad == 2


def test_ledger_replay_breaker_is_opt_in():
    flood = {DROP_REPLAYED_SIG: 8}
    off = ByzantineLedger(
        ByzantineConfig(min_samples=4, max_bad_rate=0.5, quarantine_replays=False)
    )
    off.note_frame("p", 0, flood, now=0.0)
    # replays are counted and surfaced but never trip the default breaker
    # (watchdog re-offers are honest same-peer repeats)
    assert not off.quarantined("p", now=0.1)
    assert off.snapshot(now=0.1)["peers"]["p"]["drops"] == flood

    on = ByzantineLedger(
        ByzantineConfig(min_samples=4, max_bad_rate=0.5, quarantine_replays=True)
    )
    on.note_frame("p", 0, flood, now=0.0)
    assert on.quarantined("p", now=0.1)


def test_ledger_attributes_origins_and_charges_scoreboard():
    sb = FakeScoreboard()
    led = ByzantineLedger(ByzantineConfig(strike_penalty=0.75), scoreboard=sb)
    led.register_peer(7, "peer-a")
    led.register_peer(9, "peer-b")
    # two verdicts for peer-a, one for peer-b; 0 = local/RPC/WAL ingest
    # and 42 was never registered: both must be skipped, not crash
    led.note_invalid_origins([7, 7, 9, 0, 42], now=1.0)
    assert led.strikes_of("peer-a") == 2
    assert led.strikes_of("peer-b") == 1
    assert sb.total("peer-a") == 2 * 0.75
    assert sb.total("peer-b") == 0.75
    snap = led.snapshot(now=1.0)
    assert snap["strikes"] == 3
    assert snap["peers"]["peer-a"]["invalid"] == 2


def test_ledger_verdict_flood_trips_once_and_charges_trip_penalty():
    sb = FakeScoreboard()
    led = ByzantineLedger(
        ByzantineConfig(
            min_samples=4, max_bad_rate=0.5, strike_penalty=0.5,
            quarantine_penalty=16.0, quarantine_secs=30.0,
        ),
        scoreboard=sb,
    )
    led.register_peer(1, "flooder")
    led.note_invalid_origins([1, 1, 1, 1], now=0.0)
    assert led.quarantined("flooder", now=0.1)
    assert led.snapshot(now=0.1)["quarantines"] == 1
    assert sb.total("flooder") == 4 * 0.5 + 16.0
    # more verdicts while serving the sentence: strikes accrue, but no
    # re-trip (and no second quarantine_penalty) until it expires
    led.note_invalid_origins([1, 1, 1, 1], now=1.0)
    assert led.snapshot(now=1.1)["quarantines"] == 1
    assert sb.total("flooder") == 8 * 0.5 + 16.0


def test_ledger_sync_strike_quarantines_without_double_charge():
    sb = FakeScoreboard()
    led = ByzantineLedger(ByzantineConfig(), scoreboard=sb)
    led.note_sync_strike("forger", now=0.0)
    # a peer proven to forge sync data loses its vote-gossip privileges
    assert led.quarantined("forger", now=0.1)
    snap = led.snapshot(now=0.1)
    assert snap["peers"]["forger"]["sync_strikes"] == 1
    assert snap["peers"]["forger"]["quarantines"] == 1
    # the sync client already charged the scoreboard for this offense;
    # the ledger must not double-charge it
    assert sb.calls == []


# -- TxVotePool origin bookkeeping ----------------------------------------


def test_pool_origin_set_by_the_one_ingest_core():
    pvs, _vals = make_pvs(4)
    pool = TxVotePool(MempoolConfig(cache_size=100))
    v1 = sign_vote(pvs[0], b"origin-a")
    v2 = sign_vote(pvs[1], b"origin-b")
    v3 = sign_vote(pvs[2], b"origin-c")
    pool.check_tx(v1, tx_info=TxInfo(sender_id=5))       # the one-vote call
    pool.check_tx_many([v2], tx_info=TxInfo(sender_id=7))  # the frame call
    pool.check_tx(v3)  # local ingest: no peer to strike
    keys = [v.vote_key() for v in (v1, v2, v3)]
    assert pool.origins_of(keys) == [5, 7, 0]


def test_pool_add_sender_codes_and_origin_stability():
    pvs, _vals = make_pvs(4)
    pool = TxVotePool(MempoolConfig(cache_size=100))
    v = sign_vote(pvs[0], b"codes")
    pool.check_tx(v, tx_info=TxInfo(sender_id=3))
    key = v.vote_key()
    assert pool.add_sender(key, 4) == TxVotePool.SENDER_ADDED
    assert pool.add_sender(key, 4) == TxVotePool.SENDER_REPEAT
    # the origin peer re-sending is also a repeat...
    assert pool.add_sender(key, 3) == TxVotePool.SENDER_REPEAT
    # ...and extra senders never rewrite the attribution
    assert pool.origins_of([key]) == [3]
    pool.remove([key])
    assert pool.add_sender(key, 4) == TxVotePool.SENDER_GONE
    assert pool.origins_of([key]) == [0]
    # truthiness contract for pre-ledger callers: only GONE falls through
    assert not TxVotePool.SENDER_GONE
    assert TxVotePool.SENDER_ADDED and TxVotePool.SENDER_REPEAT


# -- engine -> ledger flow -------------------------------------------------


def test_engine_attributes_invalid_verdicts_to_origin():
    pvs, vals = make_pvs(4)
    flow, mempool, _commit, votepool, _store, app, _bus = make_engine(
        vals, use_device=False
    )
    sb = FakeScoreboard()
    led = ByzantineLedger(ByzantineConfig(strike_penalty=0.75), scoreboard=sb)
    led.register_peer(5, "flooder")
    flow.on_invalid_votes = led.note_invalid_origins

    tx = b"attr=1"
    mempool.check_tx(tx)
    for pv in pvs[:3]:
        votepool.check_tx(sign_vote(pv, tx))
    garbage = sign_vote(pvs[3], tx)
    garbage.signature = bytes(64)
    votepool.check_tx(garbage, tx_info=TxInfo(sender_id=5))
    flow.step()

    # honest quorum committed; the forged vote struck its relaying peer
    assert app.tx_count == 1
    assert led.strikes_of("flooder") == 1
    assert led.snapshot()["peers"]["flooder"]["invalid"] == 1
    assert sb.total("flooder") == 0.75

    # a locally-ingested garbage vote (origin 0) strikes nobody
    tx2 = b"attr=2"
    mempool.check_tx(tx2)
    bad_local = sign_vote(pvs[0], tx2)
    bad_local.signature = b"\x01" * 64  # distinct forgery, distinct pool key
    votepool.check_tx(bad_local)
    flow.step()
    assert led.snapshot()["strikes"] == 1


def test_accountable_parity_batched_vs_scalar():
    """Acceptance pin: with the full accountability chain wired (per-peer
    origins on ingest + verdict attribution to a live ledger), the batched
    engine's commit decisions, app digest, and certificates remain
    byte-identical to the scalar reference on a randomized adversarial
    stream — accountability observes the verify path, never steers it."""
    import random

    rng = random.Random(1337)
    pvs, vals = make_pvs(7)  # total 70, quorum 47 -> 5 votes needed
    txs = [b"acct%d=%d" % (i, i) for i in range(12)]

    stream = []
    n_corrupt = 0
    for tx in txs:
        for vi in rng.sample(range(7), rng.randint(2, 7)):
            vote = sign_vote(pvs[vi], tx)
            if rng.random() < 0.15:
                # distinct garbage per vote so every forgery is its own
                # pool entry (and its own attributed verdict)
                vote.signature = hashlib.sha256(
                    b"corrupt%d" % len(stream)
                ).digest() * 2
                n_corrupt += 1
            stream.append(vote)
    rng.shuffle(stream)

    # scalar reference engine: one vote at a time, no accountability
    flow_s, mem_s, _cs, _ps, store_s, app_s, _ = make_engine(vals, use_device=False)
    for tx in txs:
        mem_s.check_tx(tx)
    for v in stream:
        flow_s.try_add_vote(v.copy())

    # batched engine with the ledger wired and every vote peer-attributed
    sb = FakeScoreboard()
    led = ByzantineLedger(ByzantineConfig(), scoreboard=sb)
    for pid, nid in ((1, "relay-1"), (2, "relay-2"), (3, "relay-3")):
        led.register_peer(pid, nid)
    flow_b, mem_b, _cb, pool_b, store_b, app_b, _ = make_engine(
        vals, use_device=False, max_batch=17
    )
    flow_b.on_invalid_votes = led.note_invalid_origins
    for tx in txs:
        mem_b.check_tx(tx)
    for i, v in enumerate(stream):
        pool_b.check_tx(v, tx_info=TxInfo(sender_id=1 + i % 3))
    while flow_b.step():
        pass

    assert app_b.tx_count == app_s.tx_count
    assert app_b.state == app_s.state
    assert app_b.digest == app_s.digest  # commit ORDER identical
    for tx in txs:
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        cs = store_s.load_tx_commit(tx_hash)
        cb = store_b.load_tx_commit(tx_hash)
        assert (cs is None) == (cb is None)
        if cs is not None:
            assert {c.validator_address for c in cs.commits} == {
                c.validator_address for c in cb.commits
            }
    for tx_hash, vs in flow_s.vote_sets.items():
        assert flow_b.vote_sets[tx_hash].stake() == vs.stake()
    # and the ledger saw exactly the forged deliveries, no more
    assert led.snapshot()["strikes"] == n_corrupt
    snap_peers = led.snapshot()["peers"]
    assert sum(p["invalid"] for p in snap_peers.values()) == n_corrupt


# -- reactor pre-checks: deterministic crafted frames ---------------------


def test_reactor_pre_checks_count_per_peer():
    """Unknown-validator / stale-height / replayed-signature votes die at
    the pool boundary, each counted against the relaying peer — and a
    pre-dropped frame re-delivered is re-judged (never wire-cached)."""
    rogue = ByzantineVoteGen(
        MockPV(hashlib.sha256(b"rogue-signer").digest()), "txflow-localnet"
    )
    net = LocalNet(
        2,
        use_device_verifier=False,
        # huge min_samples: accounting only, the breaker must hold fire
        byzantine_config=ByzantineConfig(min_samples=100_000),
    )
    honest = ByzantineVoteGen(net.priv_vals[0], net.chain_id)
    try:
        net.start()
        victim = net.nodes[1]
        snap = lambda: victim.byzantine_ledger.snapshot()  # noqa: E731
        drops = lambda: snap()["peers"].get("node0", {}).get("drops", {})  # noqa: E731

        # unknown validator: well-formed votes from a signer outside the set
        unknown_frame = _encode_vote_frame(
            [rogue.honest_vote(b"rogue-tx%d" % i) for i in range(3)]
        )
        net.nodes[0].switch.broadcast(CHANNEL_TXVOTE, unknown_frame)
        assert wait_until(lambda: drops().get(DROP_UNKNOWN_VALIDATOR) == 3)
        # pre-dropped segs are NOT wire-cached: redelivery is re-judged
        net.nodes[0].switch.broadcast(CHANNEL_TXVOTE, unknown_frame)
        assert wait_until(lambda: drops().get(DROP_UNKNOWN_VALIDATOR) == 6)

        # stale height: validly signed, far behind the victim's state
        victim.update_state(50)
        stale_frame = _encode_vote_frame(
            [honest.honest_vote(b"stale-tx%d" % i, height=1) for i in range(2)]
        )
        net.nodes[0].switch.broadcast(CHANNEL_TXVOTE, stale_frame)
        assert wait_until(lambda: drops().get(DROP_STALE_HEIGHT) == 2)

        # replay: a frame of fresh valid votes, sent three times — first
        # delivery kept, each repeat counted as a same-peer replay
        live_frame = _encode_vote_frame(
            [honest.honest_vote(b"live-tx%d" % i, height=50) for i in range(2)]
        )
        for _ in range(3):
            net.nodes[0].switch.broadcast(CHANNEL_TXVOTE, live_frame)
        assert wait_until(lambda: drops().get(DROP_REPLAYED_SIG) == 4)

        s = snap()
        assert s["pre_verify_drops"] == 6 + 2 + 4
        assert s["peers"]["node0"]["relayed"] >= 2  # the kept live votes
        assert not victim.byzantine_ledger.quarantined("node0")
        # the /health section and the metrics family surface the same story
        # (the monitor republishes the ledger on its tick cadence)
        assert wait_until(
            lambda: victim.health.snapshot()["byzantine"].get("pre_verify_drops")
            == 12,
            timeout=20,
        )
        exposition = victim.metrics_registry.expose()
        assert "txflow_byzantine_drop_unknown_validator 6.0" in exposition
        assert "txflow_byzantine_drop_stale_height 2.0" in exposition
        assert "txflow_byzantine_drop_replayed_sig 4.0" in exposition
    finally:
        net.stop()


# -- the tier-1 drill: survive a Byzantine vote flood ---------------------


def test_drill_byzantine_flood_localnet():
    """1-of-4 Byzantine validator (signer disarmed, floods garbage +
    stale votes through its own switch) plus a malicious non-validator
    peer (replays + unknown-signer floods). All honest txs commit with
    zero loss, every adversary is struck AND quarantined on every honest
    node, and once quarantined the flood stops reaching the device:
    < 5% of subsequently dispatched votes are invalid."""
    cfg = make_test_config()
    cfg.consensus.skip_timeout_commit = True
    # Phase 1 runs with the breaker held open (huge min_samples) so every
    # attack class provably lands in the accounting while the flood is at
    # full blast; the config object is SHARED by every node's ledger, so
    # tightening it live (phase 2) arms all breakers at once — the
    # already-poisoned windows trip on the very next judged frame.
    byz = ByzantineConfig(
        min_samples=1_000_000,
        max_bad_rate=0.5,
        stale_height_slack=8,
        quarantine_replays=True,
        replay_min_samples=1_000_000,
        replay_max_rate=0.7,
        quarantine_secs=600.0,  # outlives the assertion window
        # zero per-strike score, keeping the links up: the drill pins the
        # gossip protections; scoreboard charging is unit-tested and the
        # score-floor evict/redial cycle is sync/health-tested
        strike_penalty=0.0,
        quarantine_penalty=0.5,
    )
    net = LocalNet(
        4,
        use_device_verifier=False,
        enable_consensus=True,
        config=cfg,
        byzantine_config=byz,
        # The evil peer is SILENT until the flood phase, but honest nodes
        # gossip at it from connect: the scoreboard marks the quiet link
        # stale (stale_after 2s, -1/tick) and walks it to the score floor
        # in ~4s — evicting every evil link before the flood's drops can
        # be recorded whenever consensus reaches the flood phase late.
        # The drill pins the vote-accounting ledger; scoreboard eviction
        # has its own health/sync tests, so disarm the floor here.
        health_config=HealthConfig(
            redial_lost_peers=True, stale_penalty=0.0, score_floor=-1e9
        ),
    )
    # node0 turns Byzantine: honest fast-path signer disarmed (its
    # consensus identity stays — quorum is now exactly the 3 honest keys)
    net.nodes[0].txvote_reactor.priv_val = None
    gen0 = ByzantineVoteGen(net.priv_vals[0], net.chain_id, seed=1)
    rogue = ByzantineVoteGen(
        MockPV(hashlib.sha256(b"evil-rogue").digest()), net.chain_id, seed=2
    )
    # the malicious non-validator: a full node outside the validator set
    evil = Node(
        node_id="evil-peer",
        chain_id=net.chain_id,
        val_set=net.val_set,
        app=KVStoreApplication(),
        priv_val=None,
        node_config=NodeConfig(
            config=cfg,
            use_device_verifier=False,
            enable_consensus=False,
            sign_votes=False,
            health=False,
            sync=False,
            byzantine_config=byz,
        ),
    )

    honest_txs: list[bytes] = []
    # Forgeries target "ghost" txs that never reach any mempool: their
    # vote slots stay open forever, so every garbage signature is actually
    # judged on the verify path (votes for already-committed txs are
    # late-dropped without a verdict — free for the defender, but useless
    # for pinning attribution).
    ghost_txs = [b"ghost-target%d" % i for i in range(8)]
    targets = lambda: ghost_txs + honest_txs  # noqa: E731
    height_fn = lambda: net.nodes[1].state_view().last_block_height  # noqa: E731
    flooder = SigGarbageFlooder(
        net.nodes[0].switch, gen0, targets, height_fn,
        victim_address=net.priv_vals[1].get_address(), batch=8, interval=0.03,
    )
    staler = StaleVoteSpammer(
        net.nodes[0].switch, gen0, targets, height_fn,
        lag=1000, batch=4, interval=0.05,
    )
    rogue_flooder = SigGarbageFlooder(
        evil.switch, rogue, targets, height_fn,
        batch=12, interval=0.02,
    )
    replayer = None
    drivers = []
    honest = lambda: net.nodes[1:]  # noqa: E731

    def quarantined_everywhere(nid):
        return all(n.byzantine_ledger.quarantined(nid) for n in honest())

    def drop_everywhere(nid, reason):
        return all(
            n.byzantine_ledger.snapshot()["peers"]
            .get(nid, {}).get("drops", {}).get(reason, 0) > 0
            for n in honest()
        )

    try:
        net.start()
        evil.start()
        for n in net.nodes:
            connect_switches(evil.switch, n.switch)

        # let consensus outrun the stale slack so the stale pre-check has
        # a real horizon to enforce
        assert wait_until(lambda: height_fn() >= 10, timeout=90), height_fn()

        batch_a = [b"under-fire%d=v" % i for i in range(6)]
        honest_txs.extend(batch_a)
        for tx in batch_a:
            net.broadcast_tx(tx, node_index=1)

        # evil replays one frame of validly-signed votes forever; the votes
        # target ghost txs so the pool entries never purge and every
        # redelivery is a countable sender-repeat rather than a dup of a
        # committed vote. The frame's height sits FAR ahead of the chain:
        # consensus keeps advancing under skip_timeout_commit, and a frame
        # built at the live height crosses the stale horizon (slack 8)
        # while it is still queued behind the garbage flood on a slow CI
        # box — after which every redelivery is stale-dropped and the
        # replay class can never land in the accounting. The stale class
        # has its own dedicated spammer; this frame must stay fresh.
        h = height_fn() + 100_000
        replayer = IdenticalVoteReplayer(
            evil.switch,
            [
                ByzantineVoteGen(net.priv_vals[2], net.chain_id).honest_vote(tx, h)
                for tx in ghost_txs[:3]
            ],
            interval=0.01,
        )
        # phase 1: every adversary fires at once, breaker held open
        for d in (replayer, rogue_flooder, staler, flooder):
            d.start()
            drivers.append(d)

        # zero admitted-tx loss while the flood is at full blast
        assert net.wait_all_committed(batch_a, timeout=90)

        # every attack class lands in every honest ledger's accounting
        # (generous windows: the replay/stale frames queue behind the
        # full-blast garbage flood in a single-core CI box's ingest)
        assert wait_until(
            lambda: drop_everywhere("node0", DROP_STALE_HEIGHT), timeout=120
        )
        assert wait_until(
            lambda: drop_everywhere("evil-peer", DROP_REPLAYED_SIG), timeout=120
        )
        assert wait_until(
            lambda: drop_everywhere("evil-peer", DROP_UNKNOWN_VALIDATOR),
            timeout=120,
        )
        # ...and forged-signature verdicts attributed back to node0
        assert wait_until(
            lambda: all(
                n.byzantine_ledger.snapshot()["peers"]["node0"]["invalid"] > 0
                for n in honest()
            ),
            timeout=120,
        )
        for n in honest():
            assert n.byzantine_ledger.strikes_of("node0") > 0
        assert not any(
            n.byzantine_ledger.quarantined(nid)
            for n in honest()
            for nid in ("node0", "evil-peer")
        )

        # phase 2: arm the breakers — the poisoned windows trip on the
        # next judged frame from each adversary
        byz.min_samples = 24
        byz.replay_min_samples = 48
        assert wait_until(lambda: quarantined_everywhere("node0"), timeout=120)
        assert wait_until(lambda: quarantined_everywhere("evil-peer"), timeout=120)
        for n in honest():
            # the trip itself is a strike: a pure pre-drop flooder (never
            # judged on the device) still ends up on the strike record
            assert n.byzantine_ledger.strikes_of("evil-peer") > 0
        # the gate is absorbing the still-running flood at the front door
        assert wait_until(
            lambda: drop_everywhere("node0", DROP_QUARANTINED), timeout=30
        )
        assert wait_until(
            lambda: drop_everywhere("evil-peer", DROP_QUARANTINED), timeout=30
        )

        # post-quarantine waste bound: wait for in-flight garbage verdicts
        # to drain, then commit a fresh batch under the (blocked) flood
        def invalids():
            return [int(n.metrics.invalid_votes.value()) for n in honest()]

        stable = invalids()
        stable_since = time.monotonic()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            cur = invalids()
            if cur != stable:
                stable, stable_since = cur, time.monotonic()
            elif time.monotonic() - stable_since >= 1.0:
                break
            time.sleep(0.1)
        base = [
            (int(n.metrics.verified_votes.value()), int(n.metrics.invalid_votes.value()))
            for n in honest()
        ]

        batch_b = [b"post-quarantine%d=v" % i for i in range(6)]
        honest_txs.extend(batch_b)
        for tx in batch_b:
            net.broadcast_tx(tx, node_index=2)
        assert net.wait_all_committed(batch_b, timeout=90)

        for n, (v0, i0) in zip(honest(), base):
            dv = int(n.metrics.verified_votes.value()) - v0
            di = int(n.metrics.invalid_votes.value()) - i0
            assert dv > 0, "honest votes must still reach the device"
            rate = di / (di + dv)
            assert rate < 0.05, (
                f"{n.node_id}: post-quarantine invalid rate {rate:.3f} "
                f"(invalid {di} / dispatched {di + dv})"
            )

        # ground truth: the adversaries really were firing the whole time
        for d in drivers:
            assert d.frames > 0 and d.emitted > 0
    finally:
        for d in drivers:
            d.stop()
        evil.stop()
        net.stop()


# -- equivocator: fast path counts once, evidence path slashes ------------


def test_drill_equivocator_evidence_to_slash():
    """The TxVoteEquivocator's fast-path pairs never double-count stake
    (first-signature-wins), and the same signer's block-path conduct —
    bridged through block_evidence -> EvidencePool — is slashed on every
    node within one epoch. Post-slash, the offender's fast-path votes
    become unknown-validator pre-drops on the honest ledgers."""
    cfg = make_test_config()
    cfg.consensus.skip_timeout_commit = True
    net = LocalNet(
        4,
        use_device_verifier=False,
        enable_consensus=True,
        config=cfg,
        epoch_config=EpochConfig(length=4, slash_fraction=1.0),
    )
    offender = net.priv_vals[0]
    off_addr = offender.get_address()
    gen = ByzantineVoteGen(offender, net.chain_id)
    eq_txs: list[bytes] = []
    eq = TxVoteEquivocator(
        net.nodes[0].switch, gen, lambda: eq_txs,
        lambda: net.nodes[1].state_view().last_block_height, interval=0.02,
    )
    try:
        net.start()
        pre = b"eq-pre=v"
        eq_txs.append(pre)
        eq.start()
        net.broadcast_tx(pre)
        assert net.wait_all_committed([pre], timeout=60)
        # equivocating pairs flooded the fast path; certificates still
        # carry each validator at most once
        h = hashlib.sha256(pre).hexdigest().upper()
        for n in net.nodes:
            addrs = [v.validator_address for v in n.tx_store.load_tx_votes(h)]
            assert len(addrs) == len(set(addrs))

        ev = eq.block_evidence(height=1)
        added, err = net.nodes[1].evidence_pool.add(ev)
        assert added, err
        assert wait_until(
            lambda: all(
                n.state_view().validators.get_by_address(off_addr)[1] is None
                for n in net.nodes
            ),
            timeout=60,
        ), [n.epoch_manager.snapshot() for n in net.nodes]

        # the slashed key's still-flooding equivocation pairs now die at
        # the pre-check: unknown validator, attributed to its node
        assert wait_until(
            lambda: net.nodes[1].byzantine_ledger.snapshot()["peers"]
            .get("node0", {}).get("drops", {}).get(DROP_UNKNOWN_VALIDATOR, 0)
            > 0,
            timeout=30,
        )

        # liveness with the reduced set
        post = b"eq-post=v"
        eq_txs.append(post)
        net.broadcast_tx(post, node_index=1)
        assert net.wait_all_committed([post], timeout=60)
        h2 = hashlib.sha256(post).hexdigest().upper()
        for n in net.nodes:
            addrs = {v.validator_address for v in n.tx_store.load_tx_votes(h2)}
            assert off_addr not in addrs
    finally:
        eq.stop()
        net.stop()


# -- selective withholder: liveness adversary ------------------------------


def test_selective_withholder_cannot_block_commits():
    """A validator that signs only txs it favors: every tx still commits
    (honest stake clears quorum without it), and the withheld txs'
    certificates provably exclude its key."""
    net = LocalNet(4, use_device_verifier=False)
    withholder = SelectiveWithholder(
        net.nodes[0], lambda tx: not tx.startswith(b"victim")
    )
    withholder.install()  # disarms node0's honest signer, pre-start
    try:
        net.start()
        favored = [b"fav%d=v" % i for i in range(3)]
        victims = [b"victim%d=v" % i for i in range(3)]
        for tx in favored + victims:
            net.broadcast_tx(tx, node_index=1)
        assert net.wait_all_committed(favored + victims, timeout=60)
        assert wait_until(lambda: withholder.withheld >= len(victims), timeout=30)
        assert withholder.signed >= 1
        addr0 = net.priv_vals[0].get_address()
        for tx in victims:
            h = hashlib.sha256(tx).hexdigest().upper()
            for n in net.nodes:
                assert addr0 not in {
                    v.validator_address for v in n.tx_store.load_tx_votes(h)
                }
    finally:
        withholder.stop()
        net.stop()


# -- /health + metrics surface --------------------------------------------


def test_health_surfaces_byzantine_section():
    net = LocalNet(2, use_device_verifier=False)
    try:
        net.start()
        led = net.nodes[0].byzantine_ledger
        led.note_sync_strike("node1")
        # the monitor tick republishes the ledger into /health
        assert wait_until(
            lambda: net.nodes[0].health.snapshot()["byzantine"].get("strikes", 0)
            >= 1,
            timeout=20,
        )
        byz = net.nodes[0].health.snapshot()["byzantine"]
        assert "node1" in byz["quarantined_peers"]
        assert byz["peers"]["node1"]["sync_strikes"] == 1
        expo = net.nodes[0].metrics_registry.expose()
        assert "txflow_byzantine_strikes" in expo
        assert "txflow_byzantine_quarantines" in expo
        assert "txflow_byzantine_quarantined_peers 1.0" in expo
    finally:
        net.stop()
