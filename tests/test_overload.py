"""Overload front-door tests (ISSUE 6): admission control semantics at
the RPC edge (429 + Retry-After, byte-consistent dup replies, counted
503 connection shedding), fee/priority mempool lanes, the address-book
reconnect hook over real TCP, and the multi-process ProcNet harness.

The full overload soak (5x offered load + chaos + blackhole healing) is
``tools/soak.py --overload``; its smoke form runs here under the slow
marker.
"""

import conftest  # noqa: F401

import hashlib
import http.client
import json
import os
import socket
import subprocess
import sys
import time

import pytest

from txflow_tpu.admission import FeeLaneClassifier, parse_fee
from txflow_tpu.pool.mempool import LANE_BULK, LANE_PRIORITY, Mempool
from txflow_tpu.utils.config import MempoolConfig, test_config as make_test_config


# -- lanes: classifier + pool plumbing --


def test_parse_fee_and_classifier():
    assert parse_fee(b"fee=7;k=v") == 7
    assert parse_fee(b"k=v") == 0
    assert parse_fee(b"fee=;k=v") == 0
    assert parse_fee(b"fee=nope;k=v") == 0
    assert parse_fee(b"fee=1" + b"x" * 100) == 0  # no terminator in scan range
    clf = FeeLaneClassifier(priority_fee_threshold=3)
    assert clf(b"fee=3;k=v") == LANE_PRIORITY
    assert clf(b"fee=2;k=v") == LANE_BULK
    assert clf(b"k=v") == LANE_BULK


def test_mempool_priority_lane_log_and_reap():
    pool = Mempool(MempoolConfig(cache_size=100))
    pool.lane_of = FeeLaneClassifier(1)

    bulk = [b"b%d=v" % i for i in range(4)]
    prio = [b"fee=2;p%d=v" % i for i in range(3)]
    pool.check_tx(bulk[0])
    pool.check_tx(prio[0])
    pool.check_tx(bulk[1])
    pool.check_tx(prio[1])
    pool.check_tx(bulk[2])
    pool.check_tx(prio[2])
    pool.check_tx(bulk[3])

    assert pool.lane_size(LANE_PRIORITY) == 3
    assert pool.lane_size(LANE_BULK) == 4

    # the priority walk sees ONLY priority txs, in insertion order
    items, pos = pool.priority_entries_from(0, limit=10)
    assert [it[1] for it in items] == prio
    assert all(it[4] == LANE_PRIORITY for it in items)
    # cursor resumes (no re-delivery)
    again, _ = pool.priority_entries_from(pos, limit=10)
    assert again == []

    # the main walk now carries the lane in slot 4
    allitems, _ = pool.entries_from(0, limit=10)
    assert len(allitems) == 7
    assert sum(1 for it in allitems if it[4] == LANE_PRIORITY) == 3

    # reaps serve the priority lane FIRST, insertion order within lanes
    reaped = pool.reap_max_txs(5)
    assert reaped[:3] == prio and reaped[3:] == bulk[:2]

    # committing a priority tx updates the lane accounting
    pool.lock()
    try:
        pool.update(1, [prio[0]])
    finally:
        pool.unlock()
    assert pool.lane_size(LANE_PRIORITY) == 2
    assert pool.size() == 6


def test_bulk_rate_token_bucket():
    """cfg.bulk_rate caps BULK admissions per second (token bucket);
    priority ignores the bucket entirely."""
    from txflow_tpu.admission import (
        AdmissionConfig,
        AdmissionController,
        ErrOverloaded,
    )

    pool = Mempool(MempoolConfig(cache_size=100))
    adm = AdmissionController(
        pool, cfg=AdmissionConfig(bulk_rate=2.0, bulk_burst=2.0)
    )
    pool.lane_of = adm.lane_of

    def key(tx):
        return hashlib.sha256(tx).digest()

    t0 = 1000.0
    # burst depth 2: two bulk admits pass, the third sheds
    assert adm.admit_rpc(b"b0=v", key(b"b0=v"), now=t0) == LANE_BULK
    assert adm.admit_rpc(b"b1=v", key(b"b1=v"), now=t0) == LANE_BULK
    with pytest.raises(ErrOverloaded):
        adm.admit_rpc(b"b2=v", key(b"b2=v"), now=t0)
    assert adm.metrics.rejected_overload.value() == 1
    # priority is never rate-capped
    assert adm.admit_rpc(b"fee=2;p=v", key(b"fee=2;p=v"), now=t0) == LANE_PRIORITY
    # tokens refill at bulk_rate: +0.5s -> one more bulk admit
    assert adm.admit_rpc(b"b2=v", key(b"b2=v"), now=t0 + 0.5) == LANE_BULK
    with pytest.raises(ErrOverloaded):
        adm.admit_rpc(b"b3=v", key(b"b3=v"), now=t0 + 0.5)
    # a shed tx was never pushed into the dedup: the retry is not a dup
    assert adm.admit_rpc(b"b3=v", key(b"b3=v"), now=t0 + 2.0) == LANE_BULK


def test_priority_sender_budget_fairness():
    """One whale tagged ``from=<id>;`` can't starve the priority lane:
    past its per-sender token budget it loses the lane's unconditional
    admission and is subjected to the bulk shed rules. Other senders and
    untagged txs are untouched, and lane ASSIGNMENT never changes."""
    from txflow_tpu.admission import (
        AdmissionConfig,
        AdmissionController,
        ErrOverloaded,
    )
    from txflow_tpu.admission.classifier import parse_sender

    assert parse_sender(b"fee=2;from=alice;k=v") == "alice"
    assert parse_sender(b"fee=2;k=v") == ""
    assert parse_sender(b"fee=2;from=alice") == ""  # no terminator

    from txflow_tpu.utils.metrics import Registry

    pool = Mempool(MempoolConfig(cache_size=100))
    adm = AdmissionController(
        pool,
        cfg=AdmissionConfig(priority_sender_rate=1.0, priority_sender_burst=1.0),
        registry=Registry(),  # keep absolute counter asserts isolated
    )
    pool.lane_of = adm.lane_of
    # force the shed verdict deterministically (storage degraded): an
    # over-budget priority sender gets exactly the bulk treatment
    adm.degraded_source = lambda: True

    def key(tx):
        return hashlib.sha256(tx).digest()

    t0 = 1000.0
    whale = [b"fee=2;from=alice;w%d=v" % i for i in range(3)]
    assert adm.admit_rpc(whale[0], key(whale[0]), now=t0) == LANE_PRIORITY
    with pytest.raises(ErrOverloaded):
        adm.admit_rpc(whale[1], key(whale[1]), now=t0)
    assert adm.metrics.priority_sender_limited.value() >= 1
    assert adm.metrics.priority_sender_shed.value() >= 1
    # a different tagged sender has its own budget
    other = b"fee=2;from=bob;k=v"
    assert adm.admit_rpc(other, key(other), now=t0) == LANE_PRIORITY
    # untagged priority txs are exempt (no sender identity to budget)
    untagged = b"fee=2;solo=v"
    assert adm.admit_rpc(untagged, key(untagged), now=t0) == LANE_PRIORITY
    # tokens refill: the whale is priority again a second later
    assert adm.admit_rpc(whale[2], key(whale[2]), now=t0 + 1.5) == LANE_PRIORITY
    assert adm.metrics.priority_sender_tracked.value() == 2.0


def test_priority_sender_budget_disabled_by_default():
    from txflow_tpu.admission import AdmissionConfig, AdmissionController
    from txflow_tpu.utils.metrics import Registry

    pool = Mempool(MempoolConfig(cache_size=100))
    adm = AdmissionController(pool, cfg=AdmissionConfig(), registry=Registry())
    pool.lane_of = adm.lane_of
    adm.degraded_source = lambda: True  # even while shedding bulk ...
    t0 = 1000.0
    for i in range(10):
        tx = b"fee=2;from=alice;d%d=v" % i
        # ... rate 0 = no per-sender budget: priority admits untouched
        assert adm.admit_rpc(tx, hashlib.sha256(tx).digest(), now=t0) == LANE_PRIORITY
    assert adm._sender_buckets == {}


def test_vote_pool_priority_lane_and_eviction():
    """Priority-tx votes ride the vote pool's priority log, and when the
    pool is FULL a priority vote evicts the oldest bulk vote instead of
    bouncing (a bounced vote is a quorum signature lost)."""
    from txflow_tpu.pool.txvotepool import TxVotePool
    from txflow_tpu.types.tx_vote import TxVote

    prio_keys = {hashlib.sha256(b"fee=2;p=v").digest()}

    def mk_vote(i, tx_key):
        return TxVote(
            height=0,
            tx_hash=tx_key.hex().upper(),
            tx_key=tx_key,
            timestamp_ns=i + 1,
            validator_address=b"\x01" * 20,
            # vote_key() is sha256(signature): keep them distinct
            signature=i.to_bytes(2, "big") * 32,
        )

    pool = TxVotePool(MempoolConfig(size=3, cache_size=100))
    pool.lane_of_vote = lambda v: (
        LANE_PRIORITY if v.tx_key in prio_keys else LANE_BULK
    )

    bulk_votes = [
        mk_vote(i, hashlib.sha256(b"b%d=v" % i).digest()) for i in range(3)
    ]
    for v in bulk_votes:
        pool.check_tx(v)
    assert pool.size() == 3  # full

    pv = mk_vote(10, next(iter(prio_keys)))
    pool.check_tx(pv)  # no raise: evicts the oldest bulk vote
    assert pool.size() == 3
    assert not pool.has(bulk_votes[0].vote_key())
    assert pool.has(pv.vote_key())
    # the evicted vote left the dedup cache too: regossip can re-deliver
    assert not pool.in_cache(bulk_votes[0].vote_key())

    # the priority walk sees ONLY the priority vote
    items, pos = pool.priority_entries_from(0, limit=10)
    assert [k for k, _v, _h, _s in items] == [pv.vote_key()]
    again, _ = pool.priority_entries_from(pos, limit=10)
    assert again == []

    # batched ingest path: bulk bounces while full, priority evicts
    from txflow_tpu.pool.mempool import ErrMempoolIsFull

    b4 = mk_vote(11, hashlib.sha256(b"b4=v").digest())
    p2k = hashlib.sha256(b"fee=2;p2=v").digest()
    prio_keys.add(p2k)
    p2 = mk_vote(12, p2k)
    errs = pool.check_tx_many([b4, p2])
    assert isinstance(errs[0], ErrMempoolIsFull)
    assert errs[1] is None
    assert pool.has(p2.vote_key())


# -- RPC edge semantics --


def _single_node(mempool_size=10, admission_config=None):
    from txflow_tpu.abci.kvstore import KVStoreApplication
    from txflow_tpu.node.node import Node, NodeConfig
    from txflow_tpu.types.priv_validator import MockPV
    from txflow_tpu.types.validator import Validator, ValidatorSet

    pv = MockPV(hashlib.sha256(b"overload-val").digest())
    vs = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), 10)])
    cfg = make_test_config()
    cfg.mempool.size = mempool_size
    node = Node(
        node_id="overload-node",
        chain_id="txflow-overload",
        val_set=vs,
        app=KVStoreApplication(),
        priv_val=pv,
        node_config=NodeConfig(
            config=cfg,
            use_device_verifier=False,
            enable_consensus=False,
            rpc_port=0,
            admission_config=admission_config,
        ),
    )
    node.start()
    return node


def _http_get(addr, path):
    """(status, reason, content_type, body_bytes) without raising."""
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return (
            resp.status,
            resp.reason,
            resp.getheader("Content-Type"),
            resp.getheader("Retry-After"),
            resp.read(),
        )
    finally:
        conn.close()


def test_rpc_429_retry_after_on_high_water():
    """Pool past high water: bulk submissions shed with 429 + Retry-After
    while priority submissions keep landing (the lanes' whole point)."""
    node = _single_node(mempool_size=10)
    try:
        # fill to 90% with bulk through the trusted local edge
        for i in range(9):
            node.broadcast_tx(b"fill%d=v" % i)
        assert node.mempool.size() == 9

        status, _, ctype, retry_after, body = _http_get(
            node.rpc.addr, '/broadcast_tx?tx="shed-me=v"'
        )
        assert status == 429
        assert retry_after is not None and int(retry_after) >= 1
        assert "json" in ctype
        payload = json.loads(body)
        assert payload["error"] == "overloaded"
        assert payload["retry_after"] > 0
        assert node.admission.metrics.rejected_overload.value() >= 1

        # priority lane stays open at the same pool level
        status, _, _, _, body = _http_get(
            node.rpc.addr, '/broadcast_tx?tx="fee=5;vip=v"'
        )
        assert status == 200
        res = json.loads(body)["result"]
        assert res["code"] == 0
        assert node.mempool.lane_size(LANE_PRIORITY) == 1
        assert node.admission.metrics.admitted_priority.value() == 1

        # the shed tx never reached the pool or its cache: a retry after
        # the pool drains must succeed, not dup-bounce (step past the
        # cached pressure verdict, as a Retry-After-honoring client would)
        node.mempool.flush()
        time.sleep(node.admission.cfg.pressure_interval * 2)
        status, _, _, _, body = _http_get(
            node.rpc.addr, '/broadcast_tx?tx="shed-me=v"'
        )
        assert status == 200
        assert json.loads(body)["result"].get("duplicate") is None
    finally:
        node.stop()


def test_rpc_dup_replies_byte_consistent():
    """Edge-dedup hits and mempool-cache hits must answer with the same
    bytes: a client cannot tell (nor needs to) WHERE the dup was caught."""
    node = _single_node(mempool_size=100)
    try:
        # seed via the trusted local edge: the pool cache knows the tx,
        # the RPC edge dedup does NOT
        node.broadcast_tx(b"dup-k=v")

        # first RPC submit: admitted at the edge, then the POOL reports
        # the dup (ErrTxInCache path)
        pool_hit = _http_get(node.rpc.addr, '/broadcast_tx?tx="dup-k=v"')
        # second RPC submit: the EDGE dedup rejects before any pool work
        edge_hit = _http_get(node.rpc.addr, '/broadcast_tx?tx="dup-k=v"')

        assert pool_hit == edge_hit  # status, reason, headers, body — all
        status, _, _, _, body = edge_hit
        assert status == 200
        res = json.loads(body)["result"]
        assert res["duplicate"] is True
        assert res["hash"] == hashlib.sha256(b"dup-k=v").hexdigest().upper()
        assert node.admission.metrics.rejected_dup.value() >= 1
    finally:
        node.stop()


def test_rpc_conn_cap_sheds_with_503_and_counter():
    """Over the connection cap the listener answers a minimal 503 (not a
    bare reset) and counts the rejection in txflow_rpc_rejected_total."""
    node = _single_node(mempool_size=100)
    try:
        httpd = node.rpc._httpd
        # drain the semaphore so the next accept is over-cap
        taken = 0
        while httpd._conn_sem.acquire(blocking=False):
            taken += 1
        try:
            host, port = node.rpc.addr
            with socket.create_connection((host, port), timeout=10) as s:
                s.sendall(b"GET /health HTTP/1.1\r\nHost: x\r\n\r\n")
                s.settimeout(10)
                data = b""
                while b"\r\n\r\n" not in data:
                    chunk = s.recv(4096)
                    if not chunk:
                        break
                    data += chunk
                head, _, rest = data.partition(b"\r\n\r\n")
                assert b"503" in head.split(b"\r\n")[0]
                assert b"Retry-After: 1" in head
                n = int(
                    [
                        ln.split(b":")[1]
                        for ln in head.split(b"\r\n")
                        if ln.lower().startswith(b"content-length")
                    ][0]
                )
                while len(rest) < n:
                    chunk = s.recv(4096)
                    if not chunk:
                        break
                    rest += chunk
                assert json.loads(rest) == {"error": "too many open connections"}
        finally:
            for _ in range(taken):
                httpd._conn_sem.release()
        counter = node.metrics_registry.counter("rpc", "rejected_total")
        assert counter.value() >= 1
        assert "txflow_rpc_rejected_total" in node.metrics_registry.expose()
    finally:
        node.stop()


def test_gossip_ingest_shed_under_overload():
    """A full pool pauses BULK gossip ingest (counted) while priority
    gossip still lands — the reactor-side backpressure arm."""
    node = _single_node(mempool_size=10)
    try:
        for i in range(9):
            node.broadcast_tx(b"gfill%d=v" % i)
        adm = node.admission
        assert adm.overloaded() is True
        assert adm.admit_gossip(b"gossip-bulk=v") is False
        assert adm.metrics.rejected_gossip.value() >= 1
        assert adm.admit_gossip(b"fee=9;gossip-vip=v") is True
        assert adm.gossip_paused() is True

        # hysteresis: drain below low water -> gossip resumes
        node.mempool.flush()
        time.sleep(adm.cfg.pressure_interval * 2)
        assert adm.overloaded() is False
        assert adm.admit_gossip(b"gossip-bulk=v") is True
        assert adm.gossip_paused() is False
    finally:
        node.stop()


# -- real-TCP healing: the address-book reconnect hook --


def test_book_reconnector_heals_evicted_tcp_peer():
    from txflow_tpu.abci.kvstore import KVStoreApplication
    from txflow_tpu.node.node import Node, NodeConfig
    from txflow_tpu.p2p.pex import book_reconnector
    from txflow_tpu.types.priv_validator import MockPV
    from txflow_tpu.types.validator import Validator, ValidatorSet

    pvs = [MockPV(hashlib.sha256(b"heal-val%d" % i).digest()) for i in range(2)]
    vs = ValidatorSet([Validator.from_pub_key(pv.get_pub_key(), 10) for pv in pvs])
    by_addr = {pv.get_address(): pv for pv in pvs}
    nodes = []
    try:
        for i in range(2):
            node = Node(
                node_id=f"heal-{i}",
                chain_id="txflow-heal",
                val_set=vs,
                app=KVStoreApplication(),
                priv_val=by_addr[vs.get_by_index(i).address],
                node_config=NodeConfig(
                    config=make_test_config(),
                    use_device_verifier=False,
                    enable_consensus=False,
                    node_key_seed=hashlib.sha256(b"heal-key-%d" % i).digest(),
                ),
            )
            node.start()
            nodes.append(node)
        a, b = nodes
        # keyed TCP assembly: PEX + address book are auto-enabled and the
        # health layer's reconnector is the book-backed dial (the seed's
        # comment said "a TCP assembly would wire a dial" — now it IS)
        assert a.address_book is not None and a.pex is not None
        assert a.health.scoreboard.reconnector is not None

        host, port = b.switch.listen_tcp("127.0.0.1", 0)
        peer = a.switch.dial_tcp(host, port)
        b_id = peer.node_id
        assert b_id == b.switch.node_id
        # the PEX handshake teaches A the peer's listen address; don't
        # race it — seed the entry the way the advert would
        a.address_book.add(b_id, host, port)

        # evict (what the scoreboard does at score_floor) ...
        a.switch.stop_peer(peer, reason="test eviction")
        deadline = time.monotonic() + 10 * conftest.WAIT_FACTOR
        while (
            a.switch.get_peer(b_id) is not None
            or b.switch.get_peer(a.switch.node_id) is not None
        ):
            assert time.monotonic() < deadline, "old link never tore down"
            time.sleep(0.05)

        # ... and heal through the SAME hook the scoreboard drains
        reconnect = a.health.scoreboard.reconnector
        assert reconnect(b_id) is True
        assert a.switch.get_peer(b_id) is not None

        # unknown peer: the hook reports failure (backoff continues)
        assert book_reconnector(a.switch, a.address_book)("NOPE") is False
    finally:
        for node in nodes:
            node.stop()


# -- multi-process net (tools/soak.py --overload rides this harness) --


def test_procnet_two_process_commit():
    from txflow_tpu.node.procnet import ProcNet

    net = ProcNet(2, spec={"seed_prefix": "pn-smoke", "chain_id": "txflow-pn"})
    net.start(timeout=90)
    try:
        tx = b"pn-k=v"
        res = net.rpc_json(0, '/broadcast_tx?tx="pn-k=v"')["result"]
        tx_hash = hashlib.sha256(tx).hexdigest().upper()
        assert res["hash"] == tx_hash
        sub = net.rpc_json(1, f"/subscribe_tx?hash={tx_hash}&timeout=30")["result"]
        assert sub["committed"] is True, sub
        # both children expose admission metrics over real sockets
        assert net.metrics_value(0, "txflow_admission_admitted_bulk") >= 1
    finally:
        net.stop()


# -- txlint: the admit path must never block --


def test_txlint_flags_blocking_admit_path():
    from txflow_tpu.analysis.core import lint_source

    src = (
        "import time\n"
        "class AdmissionController:\n"
        "    def admit_rpc(self, tx, key):\n"
        "        time.sleep(0.1)\n"
        "        return 0\n"
        "    def _bulk_shed(self):\n"
        "        return self.fut.result()\n"
        "    def not_hot(self):\n"
        "        time.sleep(1.0)\n"
    )
    active, _ = lint_source(src, "txflow_tpu/admission/controller.py")
    hot = [v for v in active if v.rule == "hotpath-sync"]
    assert len(hot) == 2, [v.format() for v in hot]
    assert {4, 7} == {v.line for v in hot}
    assert all("admit-path" in v.message for v in hot)

    # the shipped controller stays clean under the same pass
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "txflow_tpu", "admission", "controller.py")) as f:
        real = f.read()
    active, _ = lint_source(real, "txflow_tpu/admission/controller.py")
    assert [v for v in active if v.rule == "hotpath-sync"] == []


# -- the full overload soak (wall-clock heavy: slow marker) --


@pytest.mark.slow
def test_overload_soak_smoke():
    """tools/soak.py --overload --smoke must pass its SLOs end to end:
    flat priority p50 under 429-shedding flood, chaos faults, and a
    blackholed node healing via the address-book re-dial."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "tools/soak.py", "--overload", "--smoke"],
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, f"\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "SOAK OK (overload)" in proc.stdout
