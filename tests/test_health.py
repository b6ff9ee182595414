"""Unit tests for the self-healing liveness layer (txflow_tpu/health/).

Everything here runs against fakes with an explicit clock — no LocalNet,
no threads, no sleeps. The live-network behavior (partition -> watchdog
re-offers + score-driven reconnects -> commit parity) is covered by
tests/test_self_healing.py.
"""

import pytest

from txflow_tpu.health import (
    DegradedModeRegistry,
    HealthConfig,
    PeerScoreBoard,
    QuorumStallWatchdog,
)
from txflow_tpu.utils.metrics import Registry

# ------------------------------------------------------------- fakes


class FakeStats:
    def __init__(self):
        self.send_attempts = 0
        self.send_ok = 0
        self.send_fail = 0
        self.recv_count = 0
        self.duplicates = 0


class FakePeer:
    def __init__(self, node_id):
        self.node_id = node_id
        self.stats = FakeStats()
        self.sent = []  # (chan_id, msg) accepted by try_send

    def try_send(self, chan_id, msg):
        self.sent.append((chan_id, msg))
        return True


class FakeSwitch:
    def __init__(self, peer_ids=()):
        self._peers = {pid: FakePeer(pid) for pid in peer_ids}
        self.stopped = []  # (node_id, reason)

    def peers(self):
        return list(self._peers.values())

    def n_peers(self):
        return len(self._peers)

    def get_peer(self, node_id):
        return self._peers.get(node_id)

    def stop_peer(self, peer, reason=None):
        self._peers.pop(peer.node_id, None)
        self.stopped.append((peer.node_id, reason))

    def add_fake_peer(self, node_id):
        p = FakePeer(node_id)
        self._peers[node_id] = p
        return p


def make_board(peer_ids=("a", "b"), reconnector=None, **cfg_kw):
    cfg_kw.setdefault("stale_after", 1.0)
    cfg_kw.setdefault("min_sends_for_stale", 2)
    cfg_kw.setdefault("stale_penalty", 1.0)
    cfg_kw.setdefault("score_floor", -2.0)
    cfg_kw.setdefault("reconnect_base", 0.5)
    cfg_kw.setdefault("reconnect_cap", 4.0)
    cfg_kw.setdefault("reconnect_jitter", 0.0)  # deterministic delays
    cfg = HealthConfig(**cfg_kw)
    sw = FakeSwitch(peer_ids)
    reg = DegradedModeRegistry(Registry())
    board = PeerScoreBoard(sw, cfg, reg, reconnector=reconnector)
    return board, sw, reg


# ------------------------------------------------- peer score board


def test_quiet_idle_link_is_not_stale():
    board, sw, _ = make_board()
    for t in range(1, 20):
        board.tick(now=float(t))
    assert all(s == 0.0 for s in board.scores().values())


def test_blackholed_link_goes_stale_and_is_evicted():
    """Outbound attempts with no inbound progress (the chaos-partition
    signature: the interceptor reports send success) decay the score to
    the floor and evict — but only because a reconnector is wired."""
    board, sw, reg = make_board(reconnector=lambda nid: False)
    peer = sw.get_peer("a")
    for t in range(1, 10):
        peer.stats.send_attempts += 3  # we keep handing it frames
        board.tick(now=float(t))
        if ("a", None) not in [(n, None) for n, _ in sw.stopped] and sw.get_peer(
            "a"
        ) is None:
            break
    assert any(n == "a" for n, _ in sw.stopped), "stale peer must be evicted"
    assert reg.peer_evictions == 1
    # healthy peer b saw no sends: untouched
    assert sw.get_peer("b") is not None


def test_no_eviction_without_reconnector():
    """An eviction with no way back would amputate the peer permanently:
    unwired boards observe scores but never act."""
    board, sw, _ = make_board(reconnector=None)
    peer = sw.get_peer("a")
    for t in range(1, 30):
        peer.stats.send_attempts += 3
        board.tick(now=float(t))
    assert sw.stopped == []
    assert board.scores()["a"] <= -2.0  # score still reflects reality


def test_inbound_progress_rewards_and_clears_staleness():
    board, sw, _ = make_board(reconnector=lambda nid: False)
    peer = sw.get_peer("a")
    # go nearly stale...
    peer.stats.send_attempts += 5
    board.tick(now=1.0)
    board.tick(now=2.5)
    s_stale = board.scores()["a"]
    assert s_stale < 0
    # ...then the peer answers: reward, staleness re-arms
    peer.stats.recv_count += 1
    board.tick(now=2.6)
    assert board.scores()["a"] > s_stale
    board.tick(now=3.0)  # no new sends since progress: not stale again
    assert board.scores()["a"] > s_stale


def test_gossip_redundancy_tolerated_excess_dups_penalized():
    """2-3x duplicate delivery is normal gossip; a peer sending ONLY
    duplicates gets the dup penalty."""
    board, sw, _ = make_board(dup_penalty=0.1)
    peer = sw.get_peer("a")
    # fresh-heavy traffic: 10 frames, 3 dups -> no penalty
    peer.stats.recv_count += 10
    peer.stats.duplicates += 3
    board.tick(now=1.0)
    rewarded = board.scores()["a"]
    assert rewarded > 0
    # dup-only traffic: penalized net of the recv reward
    peer.stats.recv_count += 10
    peer.stats.duplicates += 10
    board.tick(now=2.0)
    assert board.scores()["a"] < rewarded + board.cfg.recv_reward


def test_send_failures_penalized():
    board, sw, _ = make_board()
    peer = sw.get_peer("a")
    peer.stats.send_fail += 2
    board.tick(now=1.0)
    assert board.scores()["a"] == pytest.approx(-2 * board.cfg.send_fail_penalty)


def test_backoff_delay_exponential_and_capped():
    board, _, _ = make_board()
    delays = [board._backoff_delay(level) for level in range(6)]
    assert delays[:4] == [0.5, 1.0, 2.0, 4.0]
    assert delays[4] == delays[5] == 4.0  # capped


def test_backoff_jitter_bounded():
    board, _, _ = make_board(reconnect_jitter=0.25)
    for level in range(5):
        for _ in range(50):
            d = board._backoff_delay(level)
            base = min(0.5 * 2**level, 4.0)
            assert base * 0.75 <= d <= base * 1.25


def test_evict_reconnect_cycle_with_growing_backoff():
    """Evicted peer re-dials on schedule; repeated failures grow the
    delay; a success that then shows inbound progress resets the level."""
    calls = []
    outcome = {"ok": False}

    def reconnector(nid):
        calls.append(nid)
        return outcome["ok"]

    board, sw, reg = make_board(peer_ids=("a",), reconnector=reconnector)
    peer = sw.get_peer("a")
    peer.stats.send_attempts += 5
    board.tick(now=1.0)
    for t in (2.5, 3.0, 3.5):  # decay to the floor -> evict
        board.tick(now=t)
        if sw.get_peer("a") is None:
            break
    assert reg.peer_evictions == 1
    assert "a" in board._pending
    # first redial due at eviction + base(level 0)=0.5, fails -> level up
    board.tick(now=10.0)
    assert calls == ["a"]
    assert reg.reconnect_failures == 1
    due = board._pending["a"]
    assert due == pytest.approx(11.0)  # 10.0 + 0.5 * 2**1
    # now let the redial succeed
    outcome["ok"] = True
    board.tick(now=11.5)
    assert reg.peer_reconnects == 1
    assert "a" not in board._pending
    # reconnected peer shows progress -> backoff level clears
    p2 = sw.add_fake_peer("a")
    p2.stats.recv_count += 1
    board.tick(now=12.0)
    assert board._backoff_level.get("a") is None


def test_reconnect_skipped_when_peer_already_back():
    board, sw, reg = make_board(peer_ids=("a",), reconnector=lambda nid: True)
    board._pending["a"] = 0.0  # due immediately — but the peer is live
    board.tick(now=1.0)
    assert reg.peer_reconnects == 0
    assert "a" not in board._pending


# ------------------------------------------------------ stall watchdog


class FakeEngine:
    def __init__(self):
        self.inflight = []  # (tx_hash, stake)

    def inflight_snapshot(self):
        return list(self.inflight)


class FakeVotePool:
    def __init__(self, segs_by_tx=None):
        self.segs_by_tx = segs_by_tx or {}

    def segs_for_tx(self, tx_hash, limit=512):
        return self.segs_by_tx.get(tx_hash, [])[:limit]


class FakeMempool:
    def __init__(self, txs=None):
        self.txs = txs or {}

    def get_tx(self, tx_key):
        return self.txs.get(tx_key)


TXH = "ab" * 32  # valid hex: the watchdog derives the mempool key from it


def make_watchdog(peer_ids=("a", "b", "c"), stall_timeout=1.0):
    cfg = HealthConfig(stall_timeout=stall_timeout)
    sw = FakeSwitch(peer_ids)
    reg = DegradedModeRegistry(Registry())
    engine = FakeEngine()
    pool = FakeVotePool({TXH: [b"seg1", b"seg2"]})
    mem = FakeMempool({bytes.fromhex(TXH): b"the-tx"})
    wd = QuorumStallWatchdog(engine, pool, mem, sw, cfg, reg)
    return wd, engine, sw, reg


def test_watchdog_quiet_when_quorum_advances():
    wd, engine, sw, reg = make_watchdog()
    engine.inflight = [(TXH, 10)]
    wd.tick(now=0.0)
    engine.inflight = [(TXH, 20)]  # stake advancing: re-armed each tick
    wd.tick(now=1.5)
    engine.inflight = [(TXH, 30)]
    wd.tick(now=3.0)
    assert reg.watchdog_firings == 0
    assert all(p.sent == [] for p in sw.peers())


def test_watchdog_fires_one_peer_then_escalates_to_all():
    wd, engine, sw, reg = make_watchdog()
    engine.inflight = [(TXH, 10)]
    wd.tick(now=0.0)
    wd.tick(now=1.2)  # past stall_timeout: level-0 firing, ONE peer
    assert reg.watchdog_firings == 1
    assert reg.watchdog_escalations == 0
    targeted = [p for p in sw.peers() if p.sent]
    assert len(targeted) == 1
    # votes re-offered as one frame + the tx bytes to the same peer
    assert len(targeted[0].sent) == 2
    assert reg.reoffered_votes == 2 and reg.reoffered_txs == 1
    wd.tick(now=2.4)  # still stuck: escalated firing, ALL peers
    assert reg.watchdog_firings == 2
    assert reg.watchdog_escalations == 1
    assert all(p.sent for p in sw.peers())


def test_watchdog_paced_not_a_flood():
    wd, engine, sw, reg = make_watchdog(stall_timeout=1.0)
    engine.inflight = [(TXH, 10)]
    wd.tick(now=0.0)
    for ms in range(1, 40):  # 0.1s ticks for ~4s
        wd.tick(now=ms / 10.0)
    # one firing per stall_timeout interval, not per tick
    assert reg.watchdog_firings <= 4


def test_watchdog_forgets_committed_txs():
    wd, engine, sw, reg = make_watchdog()
    engine.inflight = [(TXH, 10)]
    wd.tick(now=0.0)
    engine.inflight = []  # committed/purged
    wd.tick(now=5.0)
    assert wd._stalls == {}
    assert reg.watchdog_firings == 0


def test_watchdog_reports_stall_onset_age_across_rearms():
    """oldest_stall_age is measured from stall ONSET: the per-firing
    re-arm paces escalation but must not hide how long the tx is stuck."""
    wd, engine, sw, reg = make_watchdog(stall_timeout=1.0)
    engine.inflight = [(TXH, 10)]
    wd.tick(now=0.0)
    wd.tick(now=1.5)  # fires, re-arms
    wd.tick(now=2.5)  # fires again
    wd.tick(now=3.4)
    snap = reg.snapshot()
    assert snap["watchdog"]["oldest_stall_age"] == pytest.approx(3.4, abs=0.01)


# ----------------------------------------------------------- registry


def test_registry_snapshot_shape_and_metrics_parity():
    reg = DegradedModeRegistry(Registry())
    reg.note_watchdog_fired(escalated=False, votes=3, txs=1)
    reg.note_watchdog_fired(escalated=True, votes=2, txs=0)
    reg.note_peer_evicted()
    reg.note_peer_reconnected()
    reg.note_reconnect_failed()
    snap = reg.snapshot(peer_scores={"a": 1.0})
    assert snap["watchdog"]["firings"] == 2
    assert snap["watchdog"]["escalations"] == 1
    assert snap["watchdog"]["reoffered_votes"] == 5
    assert snap["watchdog"]["reoffered_txs"] == 1
    assert snap["peers"]["evictions"] == 1
    assert snap["peers"]["reconnects"] == 1
    assert snap["peers"]["reconnect_failures"] == 1
    assert snap["peers"]["scores"] == {"a": 1.0}
    # /metrics and /health never disagree about totals
    m = reg.metrics
    assert m.watchdog_firings.value() == 2
    assert m.peer_evictions.value() == 1
    assert m.peer_reconnects.value() == 1


def test_health_config_validation_defaults():
    cfg = HealthConfig()
    assert cfg.tick_interval > 0
    assert cfg.reconnect_base <= cfg.reconnect_cap
    assert cfg.score_floor < 0 < cfg.score_max


def test_registry_reports_dedup_evictions():
    """/health's progress section counts what each dedup set has pushed
    out at capacity: 0 on a fresh node, then exactly the pushes beyond
    capacity (a refresh of a present key or a remove evicts nothing)."""
    from txflow_tpu.node import LocalNet

    net = LocalNet(1, use_device_verifier=False)  # never started: refresh reads state only
    node = net.nodes[0]
    fields = ("mempool_dedup_evictions", "txvote_dedup_evictions", "committed_evictions")
    reg = node.health.registry
    reg.refresh(node)
    progress = reg.snapshot()["progress"]
    assert {f: progress[f] for f in fields} == dict.fromkeys(fields, 0)

    sets = (node.mempool.cache, node.tx_vote_pool.cache, node.txflow._committed)
    for extra, lru in zip((7, 5, 3), sets):
        for i in range(lru.size + extra):
            assert lru.push(i.to_bytes(32, "big"))
        assert not lru.push((lru.size + extra - 1).to_bytes(32, "big"))  # a refresh
        lru.remove((lru.size + extra - 2).to_bytes(32, "big"))
    reg.refresh(node)
    progress = reg.snapshot()["progress"]
    assert [progress[f] for f in fields] == [7, 5, 3]
