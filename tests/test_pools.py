"""Pool mechanics, mirroring reference txvotepool/ and mempool/ tests:
availability firing (:122), serial reap vs counter app (:166), WAL (:253),
max-msg-size boundary (:305), byte accounting (:357), cache LRU behavior.
"""

import hashlib
import os
import random
import statistics
import sys
import threading
import time

import pytest

from txflow_tpu.abci import AppConns, CounterApplication, KVStoreApplication
from txflow_tpu.pool import (
    ErrMempoolIsFull,
    ErrTxInCache,
    ErrTxTooLarge,
    Mempool,
    TxInfo,
    TxVotePool,
)
from txflow_tpu.pool.txvotepool import vote_key
from txflow_tpu.types import MockPV, TxVote
from txflow_tpu.types.tx_vote import encode_tx_vote
from txflow_tpu.utils.cache import LRUCache, UnlockedLRUCache
from txflow_tpu.utils.config import MempoolConfig

CHAIN_ID = "txflow-test"


def make_vote(i: int, pv: MockPV | None = None, height: int = 1) -> TxVote:
    pv = pv or MockPV()
    tx = b"tx%d" % i
    vote = TxVote(
        height=height,
        tx_hash=hashlib.sha256(tx).hexdigest().upper(),
        tx_key=hashlib.sha256(tx).digest(),
        timestamp_ns=1700000000_000000000 + i,
        validator_address=pv.get_address(),
    )
    pv.sign_tx_vote(CHAIN_ID, vote)
    return vote


# ---- LRU cache (reference cache_test.go) ----


def test_cache_lru_eviction_and_dedup():
    c = LRUCache(3)
    k = [b"%d" % i for i in range(5)]
    assert c.push(k[0]) and c.push(k[1]) and c.push(k[2])
    assert not c.push(k[0])  # dup
    assert c.push(k[3])  # evicts k[1] (k[0] was refreshed by the dup push)
    assert k[1] not in c and k[0] in c
    c.remove(k[0])
    assert c.push(k[0])


def test_unlocked_lru_cache_matches_locked_and_guards_free_threading():
    """UnlockedLRUCache is semantically the locked cache minus the mutex;
    its lock-freedom is only sound under the GIL, so on a free-threaded
    build the constructor must hand back a locked LRUCache instead."""
    import txflow_tpu.utils.cache as cache_mod
    from txflow_tpu.utils.cache import UnlockedLRUCache

    u, l = UnlockedLRUCache(3), LRUCache(3)
    for key in [b"0", b"1", b"2", b"0", b"3", b"4"]:
        assert u.push(key) == l.push(key)
    assert len(u) == len(l) == 3
    for key in (b"0", b"2", b"3", b"4"):
        assert (key in u) == (key in l)

    # simulate a free-threaded build: construction transparently degrades
    # to the locked implementation (same API, GIL-independent safety).
    # The GIL is a property of the interpreter launch, so it is weighed
    # ONCE at import (_GIL_ENABLED) — patch the constant, not the probe.
    orig = cache_mod._GIL_ENABLED
    cache_mod._GIL_ENABLED = False
    try:
        fallback = UnlockedLRUCache(3)
        assert isinstance(fallback, LRUCache)
        assert fallback.push(b"x") and not fallback.push(b"x")
        assert isinstance(cache_mod.make_lru(3), LRUCache)
    finally:
        cache_mod._GIL_ENABLED = orig

    # make_lru is the one construction seam (txlint unlocked-lru rule):
    # GIL build -> owner-serialized unlocked cache; size<=0 -> NopCache
    assert isinstance(cache_mod.make_lru(3), UnlockedLRUCache)
    assert isinstance(cache_mod.make_lru(0), cache_mod.NopCache)


class _ListLRU:
    """The plain model: a list, oldest first."""

    def __init__(self, size: int):
        self.size, self.keys, self.evictions = size, [], 0

    def push(self, key) -> bool:
        if key in self.keys:
            self.keys.remove(key)
            self.keys.append(key)
            return False
        if len(self.keys) >= self.size:
            del self.keys[0]
            self.evictions += 1
        self.keys.append(key)
        return True

    def remove(self, key) -> None:
        if key in self.keys:
            self.keys.remove(key)


@pytest.mark.parametrize("size", [3, 64, 4096])
@pytest.mark.parametrize("cls", [LRUCache, UnlockedLRUCache])
def test_lru_set_matches_list_model(cls, size):
    """A seeded random walk of push / remove / reset / in / len: every
    return value, the eviction count and the final membership equal a
    list-based LRU's. Keys come from 1.5 capacities, so the set is full
    and evicting for most of the walk, and refreshes are common."""
    rng = random.Random(29_000 + size)
    universe = [b"k%d" % i for i in range(size + size // 2 + 1)]
    c, model = cls(size), _ListLRU(size)
    n_ops = max(3000, 10 * size)
    reset_at = {n_ops // 2, n_ops // 2 + 1}  # a reset, and one of an empty set
    for step in range(n_ops):
        key = rng.choice(universe)
        op = rng.random()
        if step in reset_at:
            c.reset()
            model.keys.clear()
        elif op < 0.70:
            assert c.push(key) == model.push(key), (step, key)
        elif op < 0.80:
            c.remove(key)
            model.remove(key)
        elif op < 0.95:
            assert (key in c) == (key in model.keys), (step, key)
        assert len(c) == len(model.keys), step
    assert c.evictions == model.evictions > 0
    assert [k for k in universe if k in c] == [k for k in universe if k in model.keys]
    # recency order too: fresh keys push the old ones out oldest first
    for i in range(size):
        oldest, full = model.keys[0], len(model.keys) >= size
        assert c.push(b"fresh%d" % i) and model.push(b"fresh%d" % i)
        assert (oldest in c) == (not full), i
    assert c.evictions == model.evictions


@pytest.mark.parametrize("cls", [LRUCache, UnlockedLRUCache])
def test_lru_eviction_cost_is_flat(cls):
    """A ratio, not a time, so that it holds on any CPU: at the flood's
    capacity (69,632) an evicting push costs at most 3 non-evicting ones,
    and 300,000 evicting pushes later it costs no more than at the start.
    (The plain-dict form read 23x and rising: each eviction scanned the
    dead slots of the evictions before it.) Medians over blocks of 10,000
    on the thread's CPU clock; the better of three walks, since the gate
    is on the code's cost and not on what else runs on the box."""
    size, block = 69_632, 10_000
    keys = [i.to_bytes(8, "big") for i in range(370_000)]  # 6 blocks fill it, 30 evict
    verdicts = []
    for _ in range(3):
        c = cls(size)
        push = c.push
        costs = []
        for lo in range(0, len(keys) - block + 1, block):
            t0 = time.thread_time()
            for key in keys[lo : lo + block]:
                push(key)
            costs.append(time.thread_time() - t0)
        n_fill = size // block  # whole blocks that never evict
        filling, full = costs[:n_fill], costs[n_fill + 1 :]
        assert c.evictions == len(keys) - size and len(c) == size
        ratio = statistics.median(full) / statistics.median(filling)
        growth = statistics.median(full[-5:]) / statistics.median(full[:5])
        verdicts.append((ratio, growth))
        if ratio <= 3.0 and growth <= 1.5:
            break
    else:
        pytest.fail(f"(evicting / non-evicting, last five / first five full blocks): {verdicts}")
    print(f"evicting / non-evicting push {ratio:.2f}, last / first {growth:.2f}")


def test_unlocked_lru_membership_is_safe_beside_an_evicting_owner():
    """The reads other threads make without the owner's mutex (`in`,
    ``TxVotePool.in_cache`` and the engine's committed-set probe) beside
    one owner that pushes, refreshes and evicts: no reader ever raises or
    sees the set over capacity, a key the owner pinned by refreshing it
    is never reported missing, and the owner's own bookkeeping comes out
    exact. A short switch interval, more threads than the box has to
    spare, bounded in time."""
    size, n_push = 256, 120_000
    c = UnlockedLRUCache(size)
    pinned = b"pinned"
    c.push(pinned)
    stop = threading.Event()
    faults: list = []

    def reader():
        try:
            while not stop.is_set():
                if pinned not in c:
                    faults.append("the pinned key read as missing")
                if len(c) > size:
                    faults.append("over capacity")
                if b"never pushed" in c:
                    faults.append("a key nobody pushed read as present")
        except Exception as e:  # surfaced below: a reader must never raise
            faults.append(repr(e))

    readers = [threading.Thread(target=reader, daemon=True) for _ in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in readers:
            t.start()
        deadline = time.monotonic() + 20
        for i in range(n_push):
            c.push(i.to_bytes(8, "big"))
            if i % 100 == 0:  # well inside `size` pushes: never the oldest
                assert not c.push(pinned)
            if i % 4096 == 0:
                assert time.monotonic() < deadline
        stop.set()
        for t in readers:
            t.join(timeout=10)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in readers)
    assert faults == []
    assert len(c) == size and c.evictions == n_push + 1 - size
    assert all(i.to_bytes(8, "big") in c for i in range(n_push - size + 1, n_push))


# ---- TxVotePool ----


def test_votepool_ingest_dedup_and_bytes():
    pool = TxVotePool(MempoolConfig(cache_size=100))
    v = make_vote(0)
    pool.check_tx(v)
    assert pool.size() == 1
    assert pool.txs_bytes() == len(encode_tx_vote(v))
    with pytest.raises(ErrTxInCache):
        pool.check_tx(v, TxInfo(sender_id=7))
    # the duplicate's sender was recorded for gossip suppression
    assert pool.has_sender(vote_key(v), 7)
    pool.remove([vote_key(v)])
    assert pool.size() == 0 and pool.txs_bytes() == 0


def test_votepool_size_cap():
    pool = TxVotePool(MempoolConfig(size=2, cache_size=100))
    pool.check_tx(make_vote(0))
    pool.check_tx(make_vote(1))
    with pytest.raises(ErrMempoolIsFull):
        pool.check_tx(make_vote(2))


def test_votepool_max_msg_size_boundary():
    pool = TxVotePool(MempoolConfig(cache_size=100, max_msg_bytes=64))
    with pytest.raises(ErrTxTooLarge):
        pool.check_tx(make_vote(0))  # a full vote is ~190 bytes > 64-8


def test_votepool_availability_fires_once_per_height():
    pool = TxVotePool(MempoolConfig(cache_size=100))
    ev = pool.txs_available()
    assert not ev.is_set()
    v0, v1 = make_vote(0), make_vote(1)
    pool.check_tx(v0)
    assert ev.is_set()
    pool.check_tx(v1)  # no re-fire needed; still set
    # update to next height re-arms, and fires again since one vote remains
    pool.update(2, [v0])
    assert pool.size() == 1
    assert ev.is_set()


def test_votepool_update_removes_and_caches_committed():
    pool = TxVotePool(MempoolConfig(cache_size=100))
    pv = MockPV()
    votes = [make_vote(i, pv) for i in range(3)]
    for v in votes:
        pool.check_tx(v)
    pool.update(2, votes[:2])
    assert pool.size() == 1
    # committed votes cannot re-enter (cache)
    with pytest.raises(ErrTxInCache):
        pool.check_tx(votes[0])


def test_votepool_wal_replay(tmp_path):
    wal_path = str(tmp_path / "votepool.wal")
    pool = TxVotePool(MempoolConfig(cache_size=100), wal_path=wal_path)
    votes = [make_vote(i) for i in range(4)]
    for v in votes:
        pool.check_tx(v)
    pool.close_wal()
    assert os.path.getsize(wal_path) > 0

    pool2 = TxVotePool(MempoolConfig(cache_size=100), wal_path=wal_path)
    assert pool2.replay_wal() == 4
    assert pool2.size() == 4
    assert [v.signature for _, v in pool2.entries()] == [v.signature for v in votes]


def test_votepool_wal_torn_tail(tmp_path):
    wal_path = str(tmp_path / "votepool.wal")
    pool = TxVotePool(MempoolConfig(cache_size=100), wal_path=wal_path)
    for i in range(3):
        pool.check_tx(make_vote(i))
    pool.close_wal()
    with open(wal_path, "r+b") as f:
        f.truncate(os.path.getsize(wal_path) - 5)  # torn final frame
    pool2 = TxVotePool(MempoolConfig(cache_size=100), wal_path=wal_path)
    assert pool2.replay_wal() == 2


def test_votepool_drain_batch_order_and_skip():
    pool = TxVotePool(MempoolConfig(cache_size=100))
    votes = [make_vote(i) for i in range(5)]
    for v in votes:
        pool.check_tx(v)
    got = pool.drain_batch(3)
    assert [v.signature for _, v in got] == [v.signature for v in votes[:3]]
    skip = {got[0][0]}
    got2 = pool.drain_batch(10, skip=skip)
    assert len(got2) == 4


# ---- Mempool ----


def test_mempool_checktx_via_app_and_get_tx():
    app = KVStoreApplication()
    conns = AppConns(app)
    pool = Mempool(MempoolConfig(cache_size=100), conns.mempool)
    tx = b"k=v"
    pool.check_tx(tx)
    key = hashlib.sha256(tx).digest()
    assert pool.get_tx(key) == tx
    assert pool.get_tx(b"\x00" * 32) is None
    with pytest.raises(ErrTxInCache):
        pool.check_tx(tx)


def test_mempool_serial_counter_rejects_bad_nonce():
    app = CounterApplication(serial=True)
    conns = AppConns(app)
    pool = Mempool(MempoolConfig(cache_size=100), conns.mempool)
    pool.check_tx((0).to_bytes(8, "big"))
    pool.check_tx((1).to_bytes(8, "big"))
    # app state advanced: CheckTx compares against tx_count delivered so far;
    # a nonce below it is rejected and evicted from cache
    app.tx_count = 5
    with pytest.raises(ValueError):
        pool.check_tx((3).to_bytes(8, "big"))
    assert pool.size() == 2


def test_mempool_update_cache_semantics():
    from txflow_tpu.abci.types import ResponseDeliverTx

    pool = Mempool(MempoolConfig(cache_size=100))
    t1, t2 = b"a", b"b"
    pool.check_tx(t1)
    pool.check_tx(t2)
    pool.lock()
    pool.update(2, [t1, t2], [ResponseDeliverTx(code=0), ResponseDeliverTx(code=1)])
    pool.unlock()
    assert pool.size() == 0
    # valid committed tx stays cached; invalid one may be resubmitted
    with pytest.raises(ErrTxInCache):
        pool.check_tx(t1)
    pool.check_tx(t2)


def test_mempool_reap_bytes_and_gas():
    app = KVStoreApplication()
    conns = AppConns(app)
    pool = Mempool(MempoolConfig(cache_size=100), conns.mempool)
    txs = [b"tx-%05d" % i for i in range(10)]
    for t in txs:
        pool.check_tx(t)
    assert pool.reap_max_txs(3) == txs[:3]
    assert pool.reap_max_txs(-1) == txs
    # each tx is 8 bytes, gas 1
    assert pool.reap_max_bytes_max_gas(20, -1) == txs[:2]
    assert pool.reap_max_bytes_max_gas(-1, 4) == txs[:4]


def test_ingest_log_compaction_bounds_memory():
    """The ingest log drops its dead prefix (IngestLogPool._log_compact)
    while stable cursors keep observing every live entry exactly once."""
    from txflow_tpu.pool import base as pool_base
    from txflow_tpu.pool.txvotepool import TxVotePool

    old_threshold = pool_base.COMPACT_THRESHOLD
    pool_base.COMPACT_THRESHOLD = 16
    try:
        pool = TxVotePool(MempoolConfig(size=100000, cache_size=0))
        cursor, seen = 0, 0
        for i in range(200):
            v = TxVote(
                height=1,
                tx_hash="AB",
                tx_key=b"\x00" * 32,
                validator_address=b"x" * 20,
                signature=b"sig-%d" % i,
            )
            pool.check_tx(v)
            if i % 3 == 2:
                items, cursor = pool.entries_from(cursor, limit=1000)
                seen += len(items)
                pool.remove([k for k, _, _, _ in items])
        items, cursor = pool.entries_from(cursor, limit=1000)
        seen += len(items)
        assert seen == 200
        assert len(pool._log) < 2 * pool_base.COMPACT_THRESHOLD
        assert pool._log_base > 150
    finally:
        pool_base.COMPACT_THRESHOLD = old_threshold


# ---- check_tx vs check_tx_many parity ----
# The vote pool has ONE ingest since PR 34 (check_tx is a one-vote frame
# through check_tx_many): its parity tests hold the frame's 64-vote groups
# and the one-vote call to the same decisions. The mempool still keeps a
# hand-inlined pair (analysis/twins.json pins it to this file).


def _drive_one_by_one(check, items):
    out = []
    for it in items:
        try:
            check(it)
            out.append(None)
        except Exception as e:
            out.append(e)
    return out


def test_votepool_check_tx_many_parity():
    """One ingest sequence — accepts, a duplicate, an oversized vote, a
    pool-full rejection — pushed through check_tx one-by-one and through
    check_tx_many as a batch: identical per-position error types and
    identical final pool state (one ingest core since PR 34: the one-vote
    call raises what the frame call returns)."""
    pv = MockPV()
    v0, v1, v2, v3 = (make_vote(i, pv) for i in range(4))
    big = make_vote(99, pv)
    big.tx_hash = "A" * 1024  # encodes past max_msg_bytes
    seq = [v0, v1, v0, big, v2, v3]

    def mk():
        return TxVotePool(MempoolConfig(size=3, cache_size=100, max_msg_bytes=256))

    a, b = mk(), mk()
    errs_one = _drive_one_by_one(a.check_tx, seq)
    errs_many = b.check_tx_many(seq)

    want = [None, ErrTxInCache, ErrTxTooLarge, ErrMempoolIsFull]
    assert [type(e) for e in errs_one] == [type(e) for e in errs_many]
    assert [type(e) for e in errs_many] == [
        type(None), type(None), ErrTxInCache, ErrTxTooLarge,
        type(None), ErrMempoolIsFull,
    ], want
    assert a.size() == b.size() == 3
    assert a.txs_bytes() == b.txs_bytes()
    assert [v.signature for _, v in a.entries()] == [
        v.signature for _, v in b.entries()
    ]
    for v in (v0, v1, v2):
        assert a.has(vote_key(v)) and b.has(vote_key(v))
    # rejected votes left no residue in either pool
    for v in (big, v3):
        assert not a.has(vote_key(v)) and not b.has(vote_key(v))


def test_votepool_origin_parity():
    """Ingest-origin stamping through both calls: the sender id frozen on
    an entry at ingest (what invalid-verdict attribution charges) must be
    identical whether the vote arrived via check_tx or check_tx_many, a
    later add_sender must never rewrite it, and a local/unattributed
    ingest must read back as UNKNOWN_PEER_ID (the accountable-gossip
    origin: the record's first sender)."""
    from txflow_tpu.pool.txvotepool import UNKNOWN_PEER_ID

    pv = MockPV()
    v0, v1, v2 = (make_vote(i, pv) for i in range(3))

    def mk():
        return TxVotePool(MempoolConfig(size=10, cache_size=100))

    a, b = mk(), mk()
    a.check_tx(v0, tx_info=TxInfo(sender_id=5))
    a.check_tx(v1, tx_info=TxInfo(sender_id=7))
    a.check_tx(v2)  # local: no TxInfo
    b.check_tx_many([v0, v1], tx_info=TxInfo(sender_id=5))
    b.check_tx_many([v2])
    keys = [vote_key(v) for v in (v0, v1, v2)]
    assert a.origins_of(keys) == [5, 7, UNKNOWN_PEER_ID]
    assert b.origins_of(keys) == [5, 5, UNKNOWN_PEER_ID]
    # origin is frozen at ingest: extra senders accumulate, attribution
    # stays with the first relayer
    for p in (a, b):
        p.add_sender(keys[0], 9)
        assert p.origins_of(keys[:1]) == [p.origins_of(keys[:1])[0]]
    assert a.origins_of(keys[:1]) == [5]
    assert b.origins_of(keys[:1]) == [5]
    # unknown keys attribute to nobody
    assert a.origins_of([b"\x00" * 32]) == [UNKNOWN_PEER_ID]


def test_votepool_lane_eviction_parity():
    """Lane-aware ingest through both calls: priority votes land on the
    priority log, and at pool-full a priority vote evicts the oldest
    bulk vote while a bulk vote still bounces — identically via check_tx
    and check_tx_many (drift alarm for the lane/eviction branch)."""
    from txflow_tpu.pool.mempool import LANE_BULK, LANE_PRIORITY

    pv = MockPV()
    bulk = [make_vote(i, pv) for i in range(3)]
    prio = make_vote(50, pv)
    bulk_late = make_vote(51, pv)
    prio_keys = {prio.tx_key}

    def mk():
        p = TxVotePool(MempoolConfig(size=3, cache_size=100))
        p.lane_of_vote = lambda v: (
            LANE_PRIORITY if v.tx_key in prio_keys else LANE_BULK
        )
        return p

    seq = bulk + [bulk_late, prio]  # full -> bulk bounces, priority evicts
    a, b = mk(), mk()
    errs_one = _drive_one_by_one(a.check_tx, seq)
    errs_many = b.check_tx_many(seq)
    assert [type(e) for e in errs_one] == [type(e) for e in errs_many]
    assert [type(e) for e in errs_many] == [
        type(None), type(None), type(None), ErrMempoolIsFull, type(None),
    ]
    for p in (a, b):
        assert p.size() == 3
        assert p.has(vote_key(prio))
        assert not p.has(vote_key(bulk[0]))  # oldest bulk vote evicted
        assert not p.in_cache(vote_key(bulk[0]))  # re-deliverable
        items, _ = p.priority_entries_from(0, limit=10)
        assert [k for k, _v, _h, _s in items] == [vote_key(prio)]
        # ingest-time lane freezing (the record carries it): the
        # priority log + the bulk walk are an exact partition of the
        # live entries, even after the hook's answer changes
        assert p.prio_seq() == 1
        p.lane_of_vote = lambda v: LANE_PRIORITY  # drift: all prio now
        bitems, _ = p.bulk_entries_from(0, limit=10)
        bulk_keys = [k for k, _v, _h, _s in bitems]
        assert vote_key(prio) not in bulk_keys  # frozen prio stays out
        assert set(bulk_keys) == {
            vote_key(bulk[1]), vote_key(bulk[2])
        }  # frozen bulk stays in, despite the hook now saying priority


def test_votepool_wal_degradation_parity(tmp_path):
    """WAL EIO through both calls (the degrade branch):
    a failing WAL append must not raise out of either ingest path, must
    flip wal_degraded identically, and the votes must still land — the
    WAL is a restart-recovery aid, not the admission ledger."""
    from txflow_tpu.utils import failpoints

    pv = MockPV()
    votes = [make_vote(i, pv) for i in range(4)]

    def mk(name):
        p = TxVotePool(MempoolConfig(size=10, cache_size=100))
        p.init_wal(str(tmp_path / name))
        return p

    a, b = mk("one"), mk("many")
    try:
        failpoints.arm("wal.write", after=0)
        errs_one = _drive_one_by_one(a.check_tx, votes)
        errs_many = b.check_tx_many(votes)
    finally:
        failpoints.disarm(None)
    assert [type(e) for e in errs_one] == [type(e) for e in errs_many]
    assert all(e is None for e in errs_one)
    for p in (a, b):
        assert p.wal_degraded
        assert p.wal_errors >= 1
        assert p.size() == 4
        for v in votes:
            assert p.has(vote_key(v))
    assert [v.signature for _, v in a.entries()] == [
        v.signature for _, v in b.entries()
    ]


# ---- the one ingest core, the record and the counters (PR 34) ----


def _pool_state(p: TxVotePool):
    """Everything a reader of the pool can see, in walk order."""
    items, pos = p.entries_from(0, limit=10_000)
    keys = [k for k, *_ in items]
    return {
        "entries": [(k, v.signature, h, seg) for k, v, h, seg in items],
        "cursor": pos,
        "seq": p.seq(),
        "log": list(p._log),
        "bytes": p.txs_bytes(),
        "size": p.size(),
        "dedup": [k for k in keys if p.in_cache(k)],
        "origins": p.origins_of(keys),
        "by_tx": {h: list(ks) for h, ks in p._by_tx.items()},
        "drain": [k for k, _ in p.drain_batch(10_000)],
    }


@pytest.mark.parametrize("n_lead", [0, 60, 130])
def test_votepool_mixed_frame_parity(tmp_path, n_lead):
    """One frame that mixes primed and unprimed votes, a duplicate of a
    resident vote, a duplicate within the frame, an oversized vote and
    votes past the pool's capacity — through check_tx one by one and
    through check_tx_many as a frame, with the mix placed in the frame's
    first, first-and-second and third 64-vote lock group: the same error
    type at every position, the same records, log, dedup set, bytes and
    WAL, and the counters add up."""
    from txflow_tpu.types.tx_vote import decode_tx_vote

    pv = MockPV()
    resident = make_vote(1000, pv)
    lead = [make_vote(2000 + i, pv) for i in range(n_lead)]
    fresh = [make_vote(i, pv) for i in range(8)]
    primed = [decode_tx_vote(encode_tx_vote(make_vote(100 + i, pv))) for i in range(3)]
    assert all(v._wire_cache is not None and v._seg_cache is None for v in primed)
    big = make_vote(99, pv)
    big.tx_hash = "A" * 1024  # encodes past max_msg_bytes
    unsigned = TxVote(1, "AB" * 32, b"\x01" * 32, 5, b"\x02" * 20, None)
    frame = lead + [
        fresh[0], primed[0], resident, fresh[1], fresh[0], big, primed[1],
        unsigned, fresh[2], primed[2], fresh[3], fresh[4], fresh[5],
    ]
    size = n_lead + 1 + 8  # the resident vote, then eight of the mix fit

    def mk(name):
        p = TxVotePool(MempoolConfig(size=size, cache_size=1000, max_msg_bytes=512))
        p.init_wal(str(tmp_path / name))
        p.check_tx(cold([resident])[0], tx_info=TxInfo(sender_id=4))
        return p

    def cold(votes):  # both pools get votes in the same state
        return [v.copy() if v._wire_cache else
                TxVote(v.height, v.tx_hash, v.tx_key, v.timestamp_ns,
                       v.validator_address, v.signature) for v in votes]

    a, b = mk("one"), mk("many")
    info = TxInfo(sender_id=9)
    errs_one = _drive_one_by_one(lambda v: a.check_tx(v, tx_info=info), cold(frame))
    errs_many = b.check_tx_many(cold(frame), info)
    want = [type(None)] * n_lead + [
        type(None), type(None), ErrTxInCache, type(None), ErrTxInCache,
        ErrTxTooLarge, type(None), type(None), type(None), type(None),
        type(None), ErrMempoolIsFull, ErrMempoolIsFull,
    ]
    assert [type(e) for e in errs_one] == want
    assert [type(e) for e in errs_many] == want
    sa, sb = _pool_state(a), _pool_state(b)
    assert sa == sb
    assert sa["size"] == size and len(sa["log"]) == size
    # the duplicate recorded its sender on the resident vote, origin kept
    for p in (a, b):
        assert p.has_sender(vote_key(resident), 4) and p.has_sender(vote_key(resident), 9)
        assert p.origins_of([vote_key(resident)]) == [4]
        # a rejected vote left no residue
        for v in (big, fresh[4], fresh[5]):
            assert not p.has(vote_key(v))
        st = p.ingest_stats()
        assert st["votes"] == 1 + len(frame)
        assert st["fast"] + st["general"] + st["primed"] == st["votes"]
        assert st["primed"] == 3 and st["cpu_s"] >= 0.0
        # make_vote's hashes, keys, addresses and signatures are canonical:
        # only the oversized and the unsigned vote take encode_tx_vote
        assert st["general"] == 2
    a.close_wal()
    b.close_wal()
    from txflow_tpu.utils.wal import WAL

    wal_a = list(WAL(str(tmp_path / "one")).replay())
    wal_b = list(WAL(str(tmp_path / "many")).replay())
    assert wal_a == wal_b == [encode_tx_vote(v) for _k, v in a.entries()]


def test_votepool_check_tx_is_a_one_vote_frame():
    """check_tx raises exactly the error object check_tx_many returns for
    the vote, and accepts what it accepts."""
    pv = MockPV()
    pool = TxVotePool(MempoolConfig(size=2, cache_size=10))
    v0, v1, v2 = (make_vote(i, pv) for i in range(3))
    assert pool.check_tx(v0) is None
    with pytest.raises(ErrTxInCache):
        pool.check_tx(v0)
    assert pool.check_tx(v1) is None
    with pytest.raises(ErrMempoolIsFull) as full:
        pool.check_tx(v2)
    assert "number of txs 2 (max: 2)" in str(full.value)
    with pytest.raises(ErrMempoolIsFull):  # the capacity test comes first
        pool.check_tx(v0)
    assert pool.check_tx_many([]) == []
    assert pool.ingest_stats()["votes"] == 5


def test_votepool_record_senders_and_origin():
    """The record holds ONE sender id until a second peer delivers the
    vote, then a tuple in order of arrival: has_sender, has_sender_many,
    add_sender's three codes and origins_of read both forms, and a
    record replaced for a new sender keeps its place in the walk."""
    from txflow_tpu.pool import txvotepool as tvp

    pv = MockPV()
    v0, v1, v2 = (make_vote(i, pv) for i in range(3))
    k0, k1, k2 = (vote_key(v) for v in (v0, v1, v2))
    pool = TxVotePool(MempoolConfig(size=10, cache_size=100))
    assert pool.check_tx_many([v0, v1], TxInfo(sender_id=5)) == [None, None]
    pool.check_tx(v2)  # local: UNKNOWN_PEER_ID
    assert pool._votes[k0][tvp._SENDERS] == 5  # one int, no container
    assert pool.has_sender(k0, 5) and not pool.has_sender(k0, 6)
    assert pool.has_sender_many([k0, k1, k2, b"gone"], 5) == [True, True, False, False]
    assert pool.has_sender_many([k0, k1, k2], tvp.UNKNOWN_PEER_ID) == [False, False, True]
    assert pool.add_sender(k0, 5) == TxVotePool.SENDER_REPEAT
    assert pool.add_sender(k0, 6) == TxVotePool.SENDER_ADDED
    assert pool._votes[k0][tvp._SENDERS] == (5, 6)
    assert pool.add_sender(k0, 6) == TxVotePool.SENDER_REPEAT
    assert pool.add_sender(k0, 5) == TxVotePool.SENDER_REPEAT
    assert pool.add_sender(k0, 7) == TxVotePool.SENDER_ADDED
    # a duplicate through the ingest records its sender the same way
    assert isinstance(pool.check_tx_many([v1], TxInfo(sender_id=8))[0], ErrTxInCache)
    assert isinstance(pool.check_tx_many([v1], TxInfo(sender_id=8))[0], ErrTxInCache)
    assert pool._votes[k1][tvp._SENDERS] == (5, 8)
    assert pool.has_sender_many([k0, k1, k2], 8) == [False, True, False]
    assert pool.has_sender_many([k0, k1, k2], 7) == [True, False, False]
    assert pool.origins_of([k0, k1, k2, b"gone"]) == [5, 5, tvp.UNKNOWN_PEER_ID, tvp.UNKNOWN_PEER_ID]
    # replaced records kept their place, their segment, height and lane
    items, _ = pool.entries_from(0)
    assert [k for k, *_ in items] == [k0, k1, k2]
    assert [seg for *_, seg in items] == [
        b"".join(pool.segs_for_tx(v.tx_hash)) for v in (v0, v1, v2)
    ]
    assert pool._votes[k0][tvp._LANE] == -1 and pool._votes[k0][tvp._HEIGHT] == 0
    pool.remove([k0])
    assert pool.add_sender(k0, 9) == TxVotePool.SENDER_GONE
    assert not pool.has_sender(k0, 5)


@pytest.mark.parametrize("payload", [0, 1, 127, 128, 129, 223, 16_383, 16_384, 2_097_151, 2_097_152])
def test_votepool_wire_size_is_the_segments_payload(payload):
    """The byte accounting takes a vote's size off its segment, whatever
    the prefix's length: len(length_prefixed(x)) -> len(x)."""
    from txflow_tpu.codec import amino
    from txflow_tpu.pool.txvotepool import _wire_size

    assert _wire_size(amino.length_prefixed(bytes(payload))) == payload


def test_votepool_bytes_return_to_zero_by_every_removal_path():
    pv = MockPV()
    votes = [make_vote(i, pv) for i in range(6)]
    sizes = [len(encode_tx_vote(v)) for v in votes]
    pool = TxVotePool(MempoolConfig(size=10, cache_size=100))
    pool.check_tx_many(votes)
    assert pool.txs_bytes() == sum(sizes)
    pool.remove([vote_key(votes[0])])
    pool.update(2, [votes[1]])
    pool.lane_of_vote = lambda v: -1
    with pool._mtx:
        assert pool._evict_bulk_locked()  # the oldest left: votes[2]
    assert pool.txs_bytes() == sum(sizes[3:]) and pool.size() == 3
    assert not pool.has(vote_key(votes[2]))
    pool.remove([vote_key(v) for v in votes])
    assert pool.txs_bytes() == 0 and pool.size() == 0 and pool._by_tx == {}


def test_votepool_wal_of_a_256_vote_frame_and_its_replay(tmp_path):
    """A 256-vote frame of cold votes (one validator, 256 txs): the WAL
    holds, in ingest order, the bytes the amino rule gives (the parent's
    encoder's, spelled out in tests/test_tx_vote.py), and a replay into a
    new pool gives the same records."""
    from test_tx_vote import _wire_by_the_rule
    from txflow_tpu.utils.wal import WAL

    addr, votes = b"\x05" * 20, []
    for i in range(256):
        key = hashlib.sha256(b"frame-%d" % i).digest()
        votes.append(TxVote(i % 3, key.hex().upper(), key, 1_700_000_000_000_000_000 + 64 * i,
                            addr, hashlib.sha512(b"sig-%d" % i).digest()))
    want = [_wire_by_the_rule(v) for v in votes]
    path = str(tmp_path / "votes.wal")
    pool = TxVotePool(MempoolConfig(size=1000, cache_size=1000), wal_path=path)
    assert pool.check_tx_many(votes, TxInfo(sender_id=3)) == [None] * 256
    st = pool.ingest_stats()
    assert (st["votes"], st["fast"], st["general"], st["primed"]) == (256, 256, 0, 0)
    pool.close_wal()
    assert list(WAL(path).replay()) == want
    again = TxVotePool(MempoolConfig(size=1000, cache_size=1000), wal_path=path)
    assert again.replay_wal() == 256
    st = again.ingest_stats()  # decoded from the log: none re-encoded
    assert (st["votes"], st["fast"], st["general"], st["primed"]) == (256, 0, 0, 256)
    a, _ = pool.entries_from(0, limit=1000)
    b, _ = again.entries_from(0, limit=1000)
    assert [(k, v.signature, h, seg) for k, v, h, seg in a] == [
        (k, v.signature, h, seg) for k, v, h, seg in b
    ]
    assert [seg for *_, seg in a] == [bytes([len(w) & 0x7F | 0x80, len(w) >> 7]) + w for w in want]
    assert again.txs_bytes() == pool.txs_bytes() == sum(len(w) for w in want)
    # replayed votes have no peer to strike; the frame's had sender 3
    assert set(again.origins_of([k for k, *_ in b])) == {0}
    assert set(pool.origins_of([k for k, *_ in a])) == {3}
    again.close_wal()


def test_votepool_one_tracked_object_a_resident_vote():
    """10,000 resident votes add at most one object the collector tracks
    each besides their TxVote (the record tuple), plus one index dict a
    tx: counted with the collector frozen, so nothing is collected or
    promoted in between."""
    import gc

    n, per_tx = 10_000, 50
    addr, votes = b"\x05" * 20, []
    for i in range(n):
        key = hashlib.sha256(b"tracked-%d" % (i // per_tx)).digest()
        votes.append(TxVote(0, key.hex().upper(), key, 1_700_000_000_000_000_000 + i,
                            addr, hashlib.sha512(b"tracked-sig-%d" % i).digest()))
    pool = TxVotePool(MempoolConfig(size=2 * n, cache_size=2 * n))
    info = TxInfo(sender_id=3)
    was_enabled = gc.isenabled()
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        before = len(gc.get_objects())
        for lo in range(0, n, 250):
            assert not any(pool.check_tx_many(votes[lo : lo + 250], info))
        added = len(gc.get_objects()) - before
    finally:
        if was_enabled:
            gc.enable()
        gc.unfreeze()
    assert pool.size() == n
    # the index dicts hold only bytes and None, which the collector does
    # not track at all; the slack is the interpreter's own (frames, lists)
    assert n <= added <= n + n // per_tx + 64, added
    # a second sender makes the one int a tuple of ints: still one record
    keys = [vote_key(v) for v in votes]
    for k in keys[:100]:
        assert pool.add_sender(k, 4) == TxVotePool.SENDER_ADDED
    assert all(type(pool._votes[k]) is tuple and len(pool._votes[k]) == 5 for k in keys)


def test_votepool_ingest_counters_on_health_and_metrics():
    """txvote_ingest_votes / _cpu_s / _fast / _general / _primed: on the
    pool, on /health's progress and in the Prometheus exposition, the
    three ways adding up to the votes."""
    from txflow_tpu.node import LocalNet

    net = LocalNet(2, use_device_verifier=False)
    net.start()
    try:
        txs = [b"ingest-counter-%d=v" % i for i in range(6)]
        for tx in txs:
            net.broadcast_tx(tx)
        assert net.wait_all_committed(txs, timeout=60)
        node = net.nodes[0]
        node.health.registry.refresh(node)
        progress = node.health.snapshot()["progress"]
        st = node.tx_vote_pool.ingest_stats()
        names = ["votes", "cpu_s", "fast", "general", "primed"]
        assert [progress["txvote_ingest_" + n] <= st[n] for n in names] == [True] * 5
        votes = progress["txvote_ingest_votes"]
        assert votes >= 2 * len(txs)  # its own vote and its peer's, a tx
        assert (progress["txvote_ingest_fast"] + progress["txvote_ingest_general"]
                + progress["txvote_ingest_primed"]) == votes
        # its own votes come as objects (one pass each), its peer's decoded
        assert progress["txvote_ingest_fast"] >= len(txs)
        assert progress["txvote_ingest_primed"] >= len(txs)
        assert progress["txvote_ingest_general"] == 0
        assert progress["txvote_ingest_cpu_s"] > 0.0
        text = node.metrics_registry.expose()
        for n in names:
            assert f"txflow_health_txvote_ingest_{n} " in text, n
    finally:
        net.stop()


def test_mempool_check_tx_many_parity():
    """Mempool twin of the votepool parity test: dup, byte-budget full,
    pre_check rejection, and size-cap full must come out of check_tx and
    check_tx_many with the same error types, order, and pool state."""
    import hashlib as _h

    def mk():
        pool = Mempool(MempoolConfig(size=3, cache_size=100, max_txs_bytes=48))
        pool.pre_check = lambda tx: "contains !" if b"!" in tx else None
        return pool

    seq = [b"a=1", b"b=2", b"a=1", b"x" * 64, b"bad!", b"c=3", b"d=4"]
    a, b = mk(), mk()
    errs_one = _drive_one_by_one(a.check_tx, seq)
    errs_many = b.check_tx_many(seq)

    assert [type(e) for e in errs_one] == [type(e) for e in errs_many]
    assert [type(e) for e in errs_many] == [
        type(None), type(None), ErrTxInCache, ErrMempoolIsFull,
        ValueError, type(None), ErrMempoolIsFull,
    ]
    assert a.size() == b.size() == 3
    assert a.txs_bytes() == b.txs_bytes() == 9
    assert [t for _, t in a.entries()] == [t for _, t in b.entries()] == [
        b"a=1", b"b=2", b"c=3"
    ]
    assert a.reap_max_txs(10) == b.reap_max_txs(10)
    # a pre_check rejection must not poison the dedup cache: the same tx
    # is retryable once the pool drains (cache.remove on reject)
    for pool in (a, b):
        assert _h.sha256(b"bad!").digest() not in pool.cache


def test_pool_trace_span_parity():
    """Both calls must also agree on tracing: one accepted item = exactly
    one ingest span, duplicates and rejections record nothing — whether
    ingested one-by-one or as a batch, in both pools (sample_rate=1 so
    every tx is sampled)."""
    from txflow_tpu.trace.tracer import Tracer
    from txflow_tpu.utils.config import TraceConfig

    tcfg = TraceConfig(sample_rate=1)

    pv = MockPV()
    v0, v1 = make_vote(0, pv), make_vote(1, pv)
    vseq = [v0, v1, v0]  # accept, accept, dup

    def mk_vp():
        p = TxVotePool(MempoolConfig(size=10, cache_size=100))
        p.tracer = Tracer(tcfg)
        return p

    a, b = mk_vp(), mk_vp()
    _drive_one_by_one(a.check_tx, vseq)
    b.check_tx_many(vseq)
    for p in (a, b):
        names = [s["name"] for s in p.tracer.spans()]
        assert names == ["vote_ingest", "vote_ingest"]
        assert p.tracer.open_count() == 0
        # each accepted vote of a tx not seen before also starts that
        # tx's vote_wait, at the ingest instant (a dup starts nothing)
        firsts = {s["tx"]: s["start"] for s in p.tracer.spans()}
        assert p.tracer._first_votes == firsts and len(firsts) == 2
        # and the pool stamps its first new vote once, for pickup_wait
        assert 0.0 < p.take_first_new() <= min(firsts.values())
        assert p.take_first_new() == 0.0
    assert [s["tx"] for s in a.tracer.spans()] == [
        s["tx"] for s in b.tracer.spans()
    ]

    tseq = [b"a=1", b"b=2", b"a=1"]  # accept, accept, dup

    def mk_mp():
        p = Mempool(MempoolConfig(size=10, cache_size=100))
        p.tracer = Tracer(tcfg)
        return p

    c, d = mk_mp(), mk_mp()
    _drive_one_by_one(c.check_tx, tseq)
    d.check_tx_many(tseq)
    for p in (c, d):
        names = [s["name"] for s in p.tracer.spans()]
        assert names == ["mempool_ingest", "mempool_ingest"]
        # the mempool also anchors the e2e clock at first sight
        assert len(p.tracer._anchors) == 2
    assert [s["tx"] for s in c.tracer.spans()] == [
        s["tx"] for s in d.tracer.spans()
    ]
