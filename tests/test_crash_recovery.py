"""Crash-consistency tests: armed failpoints kill a node mid-commit; a
restart over the same durable artifacts (FileDB stores + pool WALs +
consensus WAL) must reconstruct identical state with no double delivery.

Mirrors the reference's crashingWAL restart loops and handshake replay
matrix (consensus/replay_test.go:113-180, 488-527) and the fail.Fail()
crash hooks compiled into the commit paths (txflowstate/execution.go:87,
95; state/execution.go:138-180; consensus/state.go:1277-1334). The
restart model: durable stores survive, the ABCI app restarts EMPTY and is
rebuilt by the Handshaker (block replay incl. Vtxs + fast-path commit
redelivery in commit order) — so "no double delivery" is an exactly-once
assertion over the rebuilt app's deliver stream.
"""

import conftest  # noqa: F401

import collections
import hashlib
import time

import pytest

from txflow_tpu.abci.kvstore import KVStoreApplication
from txflow_tpu.node.node import Node, NodeConfig
from txflow_tpu.store.db import FileDB
from txflow_tpu.types import TxVote
from txflow_tpu.types.priv_validator import MockPV
from txflow_tpu.types.validator import Validator, ValidatorSet
from txflow_tpu.utils import failpoints
from txflow_tpu.utils.config import test_config as make_test_config

CHAIN_ID = "test-crash"


class CountingKVStore(KVStoreApplication):
    """kvstore that records every delivered tx (exactly-once oracle)."""

    def __init__(self):
        super().__init__()
        self.delivered = collections.Counter()

    def deliver_tx(self, tx):
        self.delivered[bytes(tx)] += 1
        return super().deliver_tx(tx)


def wait_until(pred, timeout=20.0, poll=0.01):
    deadline = time.monotonic() + timeout * conftest.WAIT_FACTOR
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return False


def build_node(tmp_path, enable_consensus=False, app=None):
    """Single-validator node over durable artifacts under tmp_path."""
    pv = MockPV(hashlib.sha256(b"crash-val").digest())
    vs = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), 10)])
    cfg = make_test_config()
    cfg.consensus.skip_timeout_commit = True
    cfg.mempool.wal_dir = str(tmp_path)
    node = Node(
        node_id="crash-node",
        chain_id=CHAIN_ID,
        val_set=vs,
        app=app or CountingKVStore(),
        priv_val=pv,
        node_config=NodeConfig(
            config=cfg,
            use_device_verifier=False,
            enable_consensus=enable_consensus,
            consensus_wal_path=str(tmp_path / "consensus.wal"),
        ),
        tx_store_db=FileDB(str(tmp_path / "txstore.db")),
        state_db=FileDB(str(tmp_path / "state.db")),
        block_db=FileDB(str(tmp_path / "blocks.db")),
    )
    return node, pv


def sign_tx_vote(pv, tx):
    key = hashlib.sha256(tx).digest()
    v = TxVote(
        height=0,
        tx_hash=key.hex().upper(),
        tx_key=key,
        validator_address=pv.get_address(),
    )
    pv.sign_tx_vote(CHAIN_ID, v)
    return v


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.disarm()
    yield
    failpoints.disarm()


# -------------------------------------------------- fast-path crash points


@pytest.mark.parametrize("point", ["txflow-before-commit", "txflow-after-commit"])
def test_engine_crash_then_restart_replays_exactly_once(tmp_path, point):
    """Kill the fast path around the app Commit; the restarted node's app
    is rebuilt with each committed tx delivered exactly once, in the
    commit order persisted by the TxStore."""
    node, pv = build_node(tmp_path)
    node.start()
    committed = [b"pre-%d=v" % i for i in range(3)]
    for tx in committed:
        node.broadcast_tx(tx)
        node.tx_vote_pool.check_tx(sign_tx_vote(pv, tx))
    assert wait_until(lambda: all(node.is_committed(t) for t in committed))

    failpoints.arm(point)
    victim = b"victim=v"
    node.broadcast_tx(victim)
    node.tx_vote_pool.check_tx(sign_tx_vote(pv, victim))
    assert wait_until(lambda: failpoints.fired(point)), "failpoint must fire"
    node.stop()  # crash: partial commit state on disk
    failpoints.disarm()

    # restart over the same artifacts; handshake rebuilds the app
    app2 = CountingKVStore()
    node2, pv = build_node(tmp_path, app=app2)
    node2.start()
    try:
        # pre-crash commits: exactly once each
        for tx in committed:
            assert node2.is_committed(tx)
            assert app2.delivered[tx] == 1, f"{tx} delivered {app2.delivered[tx]}x"
        # the victim: at most once (before-commit: save_tx may or may not
        # have landed; after-commit: must be there exactly once)
        assert app2.delivered[victim] <= 1
        if point == "txflow-after-commit":
            assert node2.is_committed(victim)
            assert app2.delivered[victim] == 1
        # commit order replay preserved the persisted order prefix
        order = node2.tx_store.committed_hashes_in_order()
        want = [hashlib.sha256(t).hexdigest().upper() for t in committed]
        assert order[: len(want)] == want
        # the node still works: a fresh tx commits
        fresh = b"fresh=v"
        node2.broadcast_tx(fresh)
        node2.tx_vote_pool.check_tx(sign_tx_vote(pv, fresh))
        assert wait_until(lambda: node2.is_committed(fresh))
        # store-then-apply: the TxStore row (is_committed) lands before the
        # app delivery, so give the committer its window instead of racing it
        assert wait_until(lambda: app2.delivered[fresh] == 1)
    finally:
        node2.stop()


# ------------------------------------------------- block-path crash points


@pytest.mark.parametrize(
    "point",
    [
        "consensus-after-save-block",
        "consensus-after-end-height",
        "block-after-exec",
        "block-after-commit",
        "block-after-save",
    ],
)
def test_consensus_crash_then_restart_resumes_chain(tmp_path, point):
    """Kill consensus at every commit-path failpoint; the restarted node's
    handshake reconciles app/store/state heights and block production
    resumes with no tx delivered twice (single-validator chain: quorum of
    one, so the node commits blocks alone)."""
    node, pv = build_node(tmp_path, enable_consensus=True)
    node.start()
    txs = [b"blk-%d=v" % i for i in range(3)]
    for tx in txs:
        node.broadcast_tx(tx)
        node.tx_vote_pool.check_tx(sign_tx_vote(pv, tx))
    assert wait_until(lambda: all(node.is_committed(t) for t in txs))
    assert node.consensus.wait_for_height(2, timeout=30)

    failpoints.arm(point)
    assert wait_until(lambda: failpoints.fired(point), timeout=30), (
        f"{point} must fire during block production"
    )
    crash_store_h = node.block_store.height()
    node.stop()
    failpoints.disarm()

    app2 = CountingKVStore()
    node2, pv = build_node(tmp_path, enable_consensus=True, app=app2)
    node2.start()
    try:
        # handshake reconciled the three height domains. The restarted
        # node is already producing blocks, and a block is saved a beat
        # before the state that follows it — but both happen inside one
        # hold of the consensus lock, so under it the two reads are one
        # moment of the chain: the state sits exactly on the store's top
        # block, and names that very block
        with node2.consensus._mtx:
            st = node2.consensus.state
            store_h = node2.block_store.height()
            top = node2.block_store.load_block(store_h)
        assert st.last_block_height == store_h
        assert top.hash() == st.last_block_id
        assert store_h >= crash_store_h - 1
        # every fast-committed tx delivered exactly once into the new app
        for tx in txs:
            assert app2.delivered[tx] == 1, f"{tx} delivered {app2.delivered[tx]}x"
        # chain liveness: new blocks after restart
        h = st.last_block_height
        assert node2.consensus.wait_for_height(h + 2, timeout=30), (
            "block production must resume after crash recovery"
        )
        # and the fast path still commits new txs exactly once
        fresh = b"post-crash=v"
        node2.broadcast_tx(fresh)
        node2.tx_vote_pool.check_tx(sign_tx_vote(pv, fresh))
        assert wait_until(lambda: node2.is_committed(fresh))
        # the certificate is a decision-time fact; the ABCI apply runs a
        # beat later on the committer thread (engine commits_drained
        # docstring) — wait for the apply, then pin exactly-once
        assert wait_until(lambda: app2.delivered[fresh] >= 1)
        assert app2.delivered[fresh] == 1
    finally:
        node2.stop()


def test_handshaker_state_catchup_is_deterministic(tmp_path):
    """Crash between block save and state save ('consensus-after-save-
    block'), restart TWICE: both restarts must converge to the identical
    state bytes (the chain app hash is a pure function of block history)."""
    node, pv = build_node(tmp_path, enable_consensus=True)
    node.start()
    node.broadcast_tx(b"det=v")
    node.tx_vote_pool.check_tx(sign_tx_vote(pv, b"det=v"))
    assert wait_until(lambda: node.is_committed(b"det=v"))
    assert node.consensus.wait_for_height(2, timeout=30)
    failpoints.arm("consensus-after-save-block")
    assert wait_until(lambda: failpoints.fired("consensus-after-save-block"), timeout=30)
    node.stop()
    failpoints.disarm()

    node2, _ = build_node(tmp_path, enable_consensus=True)
    node2.start()
    state_a = node2.consensus.state.bytes()
    h_a = node2.consensus.state.last_block_height
    node2.stop()

    node3, _ = build_node(tmp_path, enable_consensus=True)
    node3.start()
    try:
        # heights can only have advanced between restarts if node2 ran
        # briefly; compare at the common height via the state store's
        # persisted snapshot determinism: same artifacts -> same state
        if node3.consensus.state.last_block_height == h_a:
            assert node3.consensus.state.bytes() == state_a
        else:
            assert node3.consensus.state.last_block_height >= h_a
    finally:
        node3.stop()


# ------------------------------------------- multi-node restart + rejoin


def test_node_restart_rejoins_and_converges(tmp_path):
    """A validator goes down mid-net, the other 3 keep committing (3/4
    quorum), and a REBUILT node over the same durable artifacts rejoins:
    handshake replays its own history into a fresh app, parallel catchup
    pulls the blocks it missed, and every tx from before/during/after the
    outage is applied exactly once everywhere."""
    from txflow_tpu.p2p import connect_switches

    pvs = [MockPV(hashlib.sha256(b"rj-%d" % i).digest()) for i in range(4)]
    vs = ValidatorSet(
        [Validator.from_pub_key(pv.get_pub_key(), 10) for pv in pvs]
    )
    by_addr = {pv.get_address(): pv for pv in pvs}
    pvs = [by_addr[v.address] for v in vs]
    cfg = make_test_config()
    cfg.consensus.skip_timeout_commit = True

    def build(i, app):
        durable = i == 2
        return Node(
            node_id=f"rj-node{i}",
            chain_id=CHAIN_ID,
            val_set=vs,
            app=app,
            priv_val=pvs[i],
            node_config=NodeConfig(
                config=cfg,
                use_device_verifier=False,
                enable_consensus=True,
                consensus_wal_path=(
                    str(tmp_path / "n2-consensus.wal") if durable else ""
                ),
            ),
            tx_store_db=FileDB(str(tmp_path / "n2-txstore.db")) if durable else None,
            state_db=FileDB(str(tmp_path / "n2-state.db")) if durable else None,
            block_db=FileDB(str(tmp_path / "n2-blocks.db")) if durable else None,
        )

    apps = [CountingKVStore() for _ in range(4)]
    nodes = [build(i, apps[i]) for i in range(4)]
    for n in nodes:
        n.start()
    for i in range(4):
        for j in range(i + 1, 4):
            connect_switches(nodes[i].switch, nodes[j].switch)
    try:
        batch_a = [b"rj-a%d=v" % i for i in range(6)]
        for tx in batch_a:
            nodes[0].broadcast_tx(tx)
        assert wait_until(
            lambda: all(n.is_committed(t) for n in nodes for t in batch_a),
            timeout=30,
        ), "batch A must commit on all 4"

        # node 2 goes down; 3/4 keeps the net live
        nodes[2].stop()
        batch_b = [b"rj-b%d=v" % i for i in range(6)]
        for tx in batch_b:
            nodes[0].broadcast_tx(tx)
        live = [nodes[0], nodes[1], nodes[3]]
        assert wait_until(
            lambda: all(n.is_committed(t) for n in live for t in batch_b),
            timeout=30,
        ), "3/4 must keep committing"
        # let blocks carrying batch B land
        h_live = max(n.consensus.state.last_block_height for n in live)

        # rebuild node 2 over its artifacts with a FRESH app; reconnect
        app2 = CountingKVStore()
        nodes[2] = build(2, app2)
        nodes[2].start()
        for j in (0, 1, 3):
            connect_switches(nodes[2].switch, nodes[j].switch)

        batch_c = [b"rj-c%d=v" % i for i in range(6)]
        for tx in batch_c:
            nodes[2].broadcast_tx(tx)
        assert wait_until(
            lambda: all(
                n.is_committed(t)
                for n in nodes
                for t in batch_a + batch_b + batch_c
            ),
            timeout=60,
        ), "rejoined net must commit everything everywhere"
        # the rejoined node caught up past the outage blocks
        assert wait_until(
            lambda: nodes[2].consensus.state.last_block_height >= h_live,
            timeout=60,
        ), "restarted node never caught up"
        # exactly-once on the rebuilt app: every batch tx delivered once
        assert wait_until(
            lambda: all(
                app2.delivered[t] == 1
                for t in batch_a + batch_b + batch_c
            ),
            timeout=30,
        ), {
            t: app2.delivered[t]
            for t in batch_a + batch_b + batch_c
            if app2.delivered[t] != 1
        }
        # content convergence with a node that never restarted
        def kv_equal():
            s0 = {
                k: v
                for k, v in apps[0].state.items()
                if k.startswith(b"rj-")
            }
            s2 = {
                k: v
                for k, v in app2.state.items()
                if k.startswith(b"rj-")
            }
            return s0 == s2

        assert wait_until(kv_equal, timeout=30), "kv state diverged after rejoin"
    finally:
        for n in nodes:
            try:
                n.stop()
            except Exception:
                pass


def test_wal_backlog_larger_than_queue_does_not_deadlock_start(tmp_path):
    """One height's WAL can hold more messages than the consensus queue's
    capacity; start() must replay them synchronously (the reference's
    catchupReplay shape) instead of enqueueing into a queue nobody drains
    yet — a 300 s churn soak wedged node revival exactly there (r5)."""
    import queue as _q
    import threading

    node, pv = build_node(tmp_path, enable_consensus=True)
    node.start()
    assert wait_until(lambda: node.consensus.state.last_block_height >= 1, 20)
    node.stop()

    # stuff the restart WAL with a same-height vote backlog
    node2, pv2 = build_node(tmp_path, enable_consensus=True)
    cs = node2.consensus  # the ConsensusState
    h = cs.state.last_block_height
    wal = cs.wal
    from txflow_tpu.types.block_vote import PREVOTE, BlockVote

    for i in range(32):
        v = BlockVote(
            height=h + 1,
            round=0,
            type=PREVOTE,
            block_id=b"\x11" * 32,
            timestamp_ns=1700000000_000000000 + i,
            validator_address=pv2.get_address(),
        )
        pv2.sign_block_vote(CHAIN_ID, v)
        wal.write_vote(v)
    backlog = wal.messages_after_end_height(h)
    assert len(backlog) > 4, "need a real backlog for the regression"
    cs._queue = _q.Queue(maxsize=4)  # far smaller than the backlog

    done = threading.Event()
    t = threading.Thread(target=lambda: (node2.start(), done.set()), daemon=True)
    t.start()
    assert done.wait(30), (
        "start() deadlocked replaying a WAL backlog larger than the queue"
    )
    node2.stop()
