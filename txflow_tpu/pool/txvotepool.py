"""TxVotePool: pending TxVotes (reference txvotepool/txvotepool.go).

Semantics preserved from the reference:
- dedup key is **sha256(signature)** (:467-469) — two votes for the same tx
  by the same validator but different sign-bytes are distinct pool entries;
- size / total-bytes caps checked before cache (:198-208);
- max single-vote size derived from the gossip msg cap (:211);
- cache hit records the new sender for in-pool votes then rejects (:213-228);
- WAL append of accepted votes (:232-243);
- ``update(height, votes)`` pushes committed votes into the cache, removes
  them from the pool and re-arms the availability notification (:329-359);
- per-height TxsAvailable firing, once (:273-307).

The batched consumer adds ``drain_batch`` — a snapshot of up to N votes in
insertion order *without* removing them (removal happens on commit/purge,
exactly like the reference's checkMaj23Routine walking the CList without
popping).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

from ..codec import amino
from ..crypto.hash import sha256
from ..trace.tracer import NULL_TRACER, SPAN_VOTE_INGEST
from ..types import TxVote, decode_tx_vote, encode_tx_vote
from ..utils.cache import make_lru
from ..utils.clock import monotonic
from ..utils.config import MempoolConfig
from ..utils.failpoints import FailpointError
from ..utils.wal import WAL
from .base import COMPACT_THRESHOLD, IngestLogPool
from .mempool import (
    LANE_PRIORITY,
    ErrMempoolIsFull,
    ErrTxInCache,
    ErrTxTooLarge,
    TxInfo,
)

UNKNOWN_PEER_ID = 0

# amino overhead allowance for a wrapped vote message (reference
# calcMaxTxSize subtracts the TxMessage envelope from MaxMsgBytes).
_MSG_OVERHEAD = 8


def vote_key(vote: TxVote) -> bytes:
    """sha256(signature) — the reference's txVoteKey (:467-469)."""
    return vote.vote_key()  # cached on the immutable vote


@dataclass(slots=True)
class _PoolVote:
    height: int
    vote: TxVote
    senders: set[int] = field(default_factory=set)
    size: int = 0  # encoded wire size, cached so removals never re-encode
    # uvarint-length-prefixed wire form, built once at ingest: the gossip
    # batch frame is a plain b"".join of these, so per-peer broadcast
    # walks never re-serialize (r4 profile: lp+append per vote per peer)
    seg: bytes = b""
    # ingest-time admission lane (LANE_PRIORITY or -1): the partition
    # key for the engine's lane-split drain. Frozen at ingest so the
    # priority log and bulk_entries_from stay an exact partition of the
    # main log even if the lane hook's answer drifts later (mempool
    # eviction, late tx arrival) — a vote is delivered by EXACTLY the
    # log its ingest classified it into. Set by BOTH ingest twins.
    lane: int = -1
    # ORIGIN: the sender whose delivery created this entry (first element
    # of `senders`, frozen at ingest). Invalid-signature verdicts are
    # attributed to the origin, not the whole sender set — later
    # duplicate senders never cost a device slot, and striking them
    # would punish honest gossip redundancy (health/byzantine.py).
    # UNKNOWN_PEER_ID = local/RPC/WAL ingest: no peer to strike.
    origin: int = 0


class TxVotePool(IngestLogPool):
    def __init__(self, config: MempoolConfig, height: int = 0, wal_path: str = ""):
        super().__init__()  # _mtx/_cond/_seq + compacted ingest log
        self.config = config
        self.height = height
        self._votes: dict[bytes, _PoolVote] = self._items  # vote_key -> entry
        # secondary index: tx_hash -> {vote_key: None} (an insertion-
        # ordered set), so segs_for_tx is O(votes-for-tx) instead of a
        # full O(pool) scan — the quorum-stall watchdog calls it per
        # stalled tx, and at bench depth the scan was the whole pool.
        # Maintained by BOTH ingest paths (check_tx's _ingest_locked and
        # the inlined check_tx_many twin) and every removal path.
        self._by_tx: dict[str, dict[bytes, None]] = {}
        self._votes_bytes = 0
        # vote-pool lanes: a vote inherits its tx's admission lane via the
        # lane_of_vote hook (Node wires mempool.lane_of_key over the
        # vote's tx_key); the priority log lets the verify engine drain
        # priority-tx votes ahead of a deep bulk backlog — the same
        # compacted-ingest-log design as Mempool._prio_log. Hook faults
        # demote to bulk: a hostile vote must not error the ingest path.
        self.lane_of_vote = None
        self._prio_log: list[bytes] = []
        self._prio_log_base = 0  # absolute position of _prio_log[0]
        # per-tx tracing (trace/tracer.py): vote arrival markers feed the
        # network-residual attribution; wired by the node, NULL_TRACER =
        # one attribute check per accepted vote
        self.tracer = NULL_TRACER
        self.cache = make_lru(config.cache_size)
        self._txs_available = threading.Event()
        self._notified_txs_available = False
        self._notify_available = False
        self.wal: WAL | None = None
        # see Mempool.wal_degraded: failed appends degrade loudly, once
        self.wal_degraded = False
        self.wal_errors = 0
        if wal_path:
            self.init_wal(wal_path)

    # -- WAL (reference InitWAL :100-123) --

    def init_wal(self, path: str) -> None:
        self.wal = WAL(path)

    def replay_wal(self) -> int:
        """Re-ingest votes from the WAL (crash recovery); returns count."""
        if self.wal is None:
            return 0
        n = 0
        for payload in self.wal.replay():
            try:
                vote = decode_tx_vote(payload)
            except Exception:
                continue
            try:
                self.check_tx(vote, write_wal=False)
                n += 1
            except (ErrTxInCache, ErrMempoolIsFull, ErrTxTooLarge):
                continue
        return n

    def close_wal(self) -> None:
        if self.wal is not None:
            self.wal.close()
            self.wal = None

    # -- introspection --

    def size(self) -> int:
        with self._mtx:
            return len(self._votes)

    def txs_bytes(self) -> int:
        with self._mtx:
            return self._votes_bytes

    def txs_available(self) -> threading.Event:
        self._notify_available = True
        return self._txs_available

    def enable_txs_available(self) -> None:
        self._notify_available = True

    def has(self, key: bytes) -> bool:
        with self._mtx:
            return key in self._votes

    def has_sender(self, key: bytes, sender_id: int) -> bool:
        with self._mtx:
            entry = self._votes.get(key)
            return entry is not None and sender_id in entry.senders

    def in_cache(self, key: bytes) -> bool:
        """Non-mutating dedup-cache membership: True means a check_tx for
        this key would be rejected with ErrTxInCache RIGHT NOW. The gossip
        receive path uses this to skip the raise-and-catch round trip for
        re-deliveries of already-committed votes (~2 extra full check_tx
        exceptions per vote per node at bench rates, r5 profile)."""
        return key in self.cache

    def has_sender_many(self, keys: list[bytes], sender_id: int) -> list[bool]:
        """has_sender for a whole gossip-walk batch under ONE lock hold
        (the per-peer broadcast walk paid a lock acquisition per vote per
        peer — r5 instrumented profile)."""
        with self._mtx:
            votes = self._votes
            out = []
            for k in keys:
                entry = votes.get(k)
                out.append(entry is not None and sender_id in entry.senders)
            return out

    # add_sender return codes (truthiness preserved for old callers:
    # 0 is still "fall back to check_tx")
    SENDER_GONE = 0  # pool no longer holds the vote
    SENDER_ADDED = 1  # new sender recorded
    SENDER_REPEAT = 2  # this peer ALREADY sent this signature (replay)

    def add_sender(self, key: bytes, sender_id: int) -> int:
        """Record that a peer holds this vote without re-ingesting it (the
        reactor's wire-level dup fast path). Returns SENDER_GONE when the
        pool no longer holds the vote — the caller must fall back to a
        real check_tx so pool-level re-accept policy stays authoritative.
        SENDER_REPEAT distinguishes the same peer re-sending an identical
        signature (replay accounting, health/byzantine.py) from a first
        delivery by an additional peer (honest gossip redundancy)."""
        with self._mtx:
            entry = self._votes.get(key)
            if entry is None:
                return self.SENDER_GONE
            if sender_id in entry.senders:
                return self.SENDER_REPEAT
            entry.senders.add(sender_id)
            return self.SENDER_ADDED

    def origins_of(self, keys: list[bytes]) -> list[int]:
        """Ingest origin (pool sender id) for each key, one lock hold;
        UNKNOWN_PEER_ID for keys already removed or locally ingested.
        The engine calls this for the invalid slice of a verify batch
        just before removing it, while still holding its own lock — so
        the entries are guaranteed present and attribution is exact."""
        with self._mtx:
            votes = self._votes
            out = []
            for k in keys:
                entry = votes.get(k)
                out.append(UNKNOWN_PEER_ID if entry is None else entry.origin)
            return out

    def _lane_quiet(self, vote: TxVote) -> int:
        """lane_of_vote with the hook-fault demotion applied (any error,
        or no hook, means bulk)."""
        if self.lane_of_vote is None:
            return -1
        try:
            return self.lane_of_vote(vote)
        except Exception:
            return -1

    def _evict_bulk_locked(self) -> bool:
        """Evict the OLDEST bulk-lane vote to make room for a priority
        vote (call under _mtx, pool full). Bulk occupancy must never
        block priority ingest: under overload the vote pool fills with
        bulk votes, and a bounced priority vote is a quorum signature
        lost — the sign walk has already moved past the tx. The evicted
        vote leaves the dedup cache too, so peer regossip re-delivers it
        once the pool drains (same retryability as a full-pool bounce)."""
        for k, e in self._votes.items():
            if self._lane_quiet(e.vote) == LANE_PRIORITY:
                continue
            self._votes.pop(k)
            self._votes_bytes -= e.size
            self._index_discard(k, e)
            self.cache.remove(k)
            return True
        return False

    # -- ingest (reference CheckTx/CheckTxWithInfo :180-261) --

    def check_tx(
        self, vote: TxVote, tx_info: TxInfo | None = None, write_wal: bool = True
    ) -> None:
        """Raises on rejection; returns None when the vote entered the pool."""
        tx_info = tx_info or TxInfo(UNKNOWN_PEER_ID)
        encoded = encode_tx_vote(vote)
        with self._mtx:
            self._ingest_locked(vote, encoded, vote_key(vote), tx_info, write_wal)
            self._notify_txs_available()

    def check_tx_many(
        self,
        votes: list[TxVote],
        tx_info: TxInfo | None = None,
        write_wal: bool = True,
    ) -> list[Exception | None]:
        """Frame-batched ingest: per-vote acceptance decisions identical
        to check_tx (same order, same errors — returned, not raised),
        with bounded lock holds (64-vote groups) and one waiter wakeup
        per group. Encode/hash for cache-miss votes runs inside the lock
        group — in the gossip path those caches are always primed at
        decode, so the in-lock work is dict stores and accounting; only
        locally constructed votes pay an in-lock encode (~1 us each,
        r5 microbench: the out-of-lock prepped-tuple design cost more in
        packaging than it saved in lock width)."""
        tx_info = tx_info or TxInfo(UNKNOWN_PEER_ID)
        out: list[Exception | None] = [None] * len(votes)
        # Inlined non-raising twin of _ingest_locked (keep the two in
        # sync): the wrapper-per-vote form — prepped tuples, try/except,
        # enumerate — measured 5.7 us/vote against the core's 4.4
        # (r5 microbench), i.e. more than half the ingest cost was
        # packaging. Error objects are built only on actual rejection.
        sid = tx_info.sender_id
        cfg = self.config
        max_size = cfg.max_msg_bytes - _MSG_OVERHEAD
        cache_push = self.cache.push
        votes_d = self._votes
        log_append = self._log_append_quiet  # one _log_notify per group
        lane_of = self.lane_of_vote
        prio_append = self._prio_log.append
        wal = self.wal if write_wal and not self.wal_degraded else None
        oset = object.__setattr__
        new = _PoolVote.__new__
        # bounded lock holds: a whole gossip frame under one lock starved
        # the drain/purge/inject paths for milliseconds (r5 instrumented
        # profile) — 64 votes ≈ a few hundred µs, keeping the pool fair
        for base in range(0, len(votes), 64):
            accepted = False
            with self._mtx:
                for i in range(base, min(base + 64, len(votes))):
                    vote = votes[i]
                    encoded = vote._wire_cache
                    if encoded is None:
                        encoded = encode_tx_vote(vote)
                    vote_size = len(encoded)
                    lane = -1
                    if lane_of is not None:
                        try:
                            lane = lane_of(vote)
                        except Exception:
                            lane = -1
                    while (
                        len(votes_d) >= cfg.size
                        or vote_size + self._votes_bytes > cfg.max_txs_bytes
                    ):
                        if lane != LANE_PRIORITY or not self._evict_bulk_locked():
                            break
                    if (
                        len(votes_d) >= cfg.size
                        or vote_size + self._votes_bytes > cfg.max_txs_bytes
                    ):
                        out[i] = ErrMempoolIsFull(
                            len(votes_d), cfg.size,
                            self._votes_bytes, cfg.max_txs_bytes,
                        )
                        continue
                    if vote_size > max_size:
                        out[i] = ErrTxTooLarge(max_size, vote_size)
                        continue
                    key = vote._vk_cache
                    if key is None:
                        key = vote.vote_key()
                    if not cache_push(key):
                        entry = votes_d.get(key)
                        if entry is not None:
                            entry.senders.add(sid)
                        out[i] = ErrTxInCache()
                        continue
                    if wal is not None:
                        try:
                            wal.write(encoded)  # txlint: allow(lock-blocking) -- WAL append order must match ingest-log order; buffered write, fsync only if sync_on_write
                        except (OSError, FailpointError):
                            self.wal_degraded = True
                            self.wal_errors += 1
                            wal = None
                    seg = vote._seg_cache
                    if seg is None:
                        seg = amino.length_prefixed(encoded)
                        oset(vote, "_seg_cache", seg)
                    entry = new(_PoolVote)
                    entry.height = self.height
                    entry.vote = vote
                    entry.senders = {sid}
                    entry.size = vote_size
                    entry.seg = seg
                    entry.lane = lane
                    entry.origin = sid
                    votes_d[key] = entry
                    by_tx = self._by_tx.get(vote.tx_hash)
                    if by_tx is None:
                        by_tx = self._by_tx[vote.tx_hash] = {}
                    by_tx[key] = None
                    log_append(key)
                    if lane == LANE_PRIORITY:
                        prio_append(key)
                    self._votes_bytes += vote_size
                    accepted = True
                    tr = self.tracer
                    if tr.active and tr.sampled(vote.tx_hash):
                        t = monotonic()
                        tr.span(vote.tx_hash, SPAN_VOTE_INGEST, t, t)
                        tr.first_vote(vote.tx_hash, t)
                if accepted:  # an all-dup group must not wake consumers
                    self._log_notify()
                    self._notify_txs_available()
        return out

    def _ingest_locked(
        self,
        vote: TxVote,
        encoded: bytes,
        key: bytes,
        tx_info: TxInfo,
        write_wal: bool,
    ) -> None:
        """One vote's acceptance decision + insertion (under self._mtx);
        availability notification is the caller's (so frames notify once)."""
        vote_size = len(encoded)
        lane = self._lane_quiet(vote)
        while (
            len(self._votes) >= self.config.size
            or vote_size + self._votes_bytes > self.config.max_txs_bytes
        ):
            if lane != LANE_PRIORITY or not self._evict_bulk_locked():
                break
        if (
            len(self._votes) >= self.config.size
            or vote_size + self._votes_bytes > self.config.max_txs_bytes
        ):
            raise ErrMempoolIsFull(
                len(self._votes),
                self.config.size,
                self._votes_bytes,
                self.config.max_txs_bytes,
            )
        max_size = self.config.max_msg_bytes - _MSG_OVERHEAD
        if vote_size > max_size:
            raise ErrTxTooLarge(max_size, vote_size)
        if not self.cache.push(key):
            entry = self._votes.get(key)
            if entry is not None:
                entry.senders.add(tx_info.sender_id)
            raise ErrTxInCache()
        if self.wal is not None and write_wal and not self.wal_degraded:
            try:
                self.wal.write(encoded)  # txlint: allow(lock-blocking) -- WAL append order must match ingest-log order; buffered write, fsync only if sync_on_write
            except (OSError, FailpointError):
                self.wal_degraded = True
                self.wal_errors += 1
        seg = vote._seg_cache
        if seg is None:
            seg = amino.length_prefixed(encoded)
            object.__setattr__(vote, "_seg_cache", seg)
        entry = _PoolVote(
            self.height, vote, {tx_info.sender_id}, vote_size, seg=seg,
            lane=lane, origin=tx_info.sender_id,
        )
        self._votes[key] = entry
        by_tx = self._by_tx.get(vote.tx_hash)
        if by_tx is None:
            by_tx = self._by_tx[vote.tx_hash] = {}
        by_tx[key] = None
        self._log_append(key)
        if lane == LANE_PRIORITY:
            self._prio_log.append(key)
        self._votes_bytes += vote_size
        tr = self.tracer
        if tr.active and tr.sampled(vote.tx_hash):
            t = monotonic()
            tr.span(vote.tx_hash, SPAN_VOTE_INGEST, t, t)
            tr.first_vote(vote.tx_hash, t)

    def _notify_txs_available(self) -> None:
        if self._notify_available and not self._notified_txs_available:
            self._notified_txs_available = True
            self._txs_available.set()

    # -- consumption --

    def reap_max_txs(self, max_: int) -> list[TxVote]:
        with self._mtx:
            if max_ < 0:
                max_ = len(self._votes)
            return [e.vote for e in list(self._votes.values())[:max_]]

    def drain_batch(self, max_: int, skip: set[bytes] | None = None) -> list[tuple[bytes, TxVote]]:
        """Snapshot up to max_ (key, vote) pairs in order, skipping keys."""
        out = []
        with self._mtx:
            for k, e in self._votes.items():
                if skip is not None and k in skip:
                    continue
                out.append((k, e.vote))
                if len(out) >= max_:
                    break
        return out

    def entries(self, after: int = 0, limit: int = -1) -> list[tuple[bytes, TxVote]]:
        """Snapshot of (key, vote) pairs in insertion order (gossip walk)."""
        with self._mtx:
            items = [(k, e.vote) for k, e in self._votes.items()]
        if limit >= 0:
            return items[after : after + limit]
        return items[after:]

    def entries_from(
        self, cursor: int, limit: int = 256
    ) -> tuple[list[tuple[bytes, TxVote, int, bytes]], int]:
        """Stable-cursor walk of live votes: (key, vote, height, wire seg)
        tuples; see IngestLogPool._entries_from for the cursor contract."""
        raw, pos = self._entries_from(cursor, limit)
        return [(k, e.vote, e.height, e.seg) for k, e in raw], pos

    def prio_seq(self) -> int:
        """Monotonic priority-ingest counter (seq()'s twin for the
        priority log): prio_seq - cursor over-counts only by removed-
        not-yet-walked entries, the same safe pending estimate the main
        log's seq gives the engine's coalescer."""
        with self._mtx:
            return self._prio_log_base + len(self._prio_log)

    def bulk_entries_from(
        self, cursor: int, limit: int = 256
    ) -> tuple[list[tuple[bytes, TxVote, int, bytes]], int]:
        """entries_from over bulk-lane votes only: the main-log walk,
        skipping entries whose INGEST-time lane was priority — those are
        the priority log's to deliver (priority_entries_from), so the
        two walks form an exact partition of the pool and the engine's
        lane-split drain visits every vote exactly once. The cursor
        still advances over skipped/dead entries (stable-cursor
        contract, IngestLogPool)."""
        out: list[tuple[bytes, TxVote, int, bytes]] = []
        with self._mtx:
            pos = max(cursor, self._log_base)
            while pos - self._log_base < len(self._log) and len(out) < limit:
                key = self._log[pos - self._log_base]
                e = self._votes.get(key)
                if e is not None and e.lane != LANE_PRIORITY:
                    out.append((key, e.vote, e.height, e.seg))
                pos += 1
        return out, pos

    def priority_entries_from(
        self, cursor: int, limit: int = 256
    ) -> tuple[list[tuple[bytes, TxVote, int, bytes]], int]:
        """entries_from over priority-lane votes only: same tuple shape
        and cursor contract, walking the priority ingest log — O(priority
        backlog), independent of how deep the bulk vote backlog is. The
        verify engine drains this BEFORE the main log so priority txs
        reach quorum at a flat latency under overload."""
        out: list[tuple[bytes, TxVote, int, bytes]] = []
        with self._mtx:
            pos = max(cursor, self._prio_log_base)
            while pos - self._prio_log_base < len(self._prio_log) and len(out) < limit:
                key = self._prio_log[pos - self._prio_log_base]
                e = self._votes.get(key)
                if e is not None:
                    out.append((key, e.vote, e.height, e.seg))
                pos += 1
        return out, pos

    def _prio_compact(self) -> None:
        """_log_compact's twin for the priority log (call under _mtx)."""
        log = self._prio_log
        n = 0
        while n < len(log) and log[n] not in self._votes:
            n += 1
        if n >= COMPACT_THRESHOLD:
            del log[:n]
            self._prio_log_base += n

    def segs_for_tx(self, tx_hash: str, limit: int = 512) -> list[bytes]:
        """Wire segments of every live vote for one tx (the quorum-stall
        watchdog's targeted re-offer input, health/watchdog.py). Walks the
        per-tx index, so cost is O(votes for this tx) — a stalled node with
        a deep pool no longer pays an O(pool) scan per watchdog firing."""
        out: list[bytes] = []
        with self._mtx:
            by_tx = self._by_tx.get(tx_hash)
            if by_tx is None:
                return out
            for k in by_tx:
                entry = self._votes.get(k)
                if entry is not None:
                    out.append(entry.seg)
                    if len(out) >= limit:
                        break
        return out

    def _index_discard(self, k: bytes, entry: _PoolVote) -> None:
        """Drop one key from the per-tx index (under self._mtx)."""
        by_tx = self._by_tx.get(entry.vote.tx_hash)
        if by_tx is not None:
            by_tx.pop(k, None)
            if not by_tx:
                del self._by_tx[entry.vote.tx_hash]

    def remove(self, keys: list[bytes], cache_too: bool = False) -> None:
        """Remove votes by key (quorum purge path)."""
        with self._mtx:
            for k in keys:
                entry = self._votes.pop(k, None)
                if entry is not None:
                    self._votes_bytes -= entry.size
                    self._index_discard(k, entry)
                if cache_too:
                    self.cache.remove(k)
            self._log_compact()
            self._prio_compact()

    # -- update on commit (reference Update :329-359) --

    def update(self, height: int, votes: list[TxVote]) -> None:
        with self._mtx:
            self.height = height
            self._notified_txs_available = False
            self._txs_available.clear()
            for v in votes:
                k = vote_key(v)
                self.cache.push(k)  # committed votes stay cached
                entry = self._votes.pop(k, None)
                if entry is not None:
                    self._votes_bytes -= entry.size
                    self._index_discard(k, entry)
            self._log_compact()
            self._prio_compact()
            if len(self._votes) > 0:
                self._notify_txs_available()

    def flush(self) -> None:
        with self._mtx:
            self._votes.clear()
            self._by_tx.clear()
            self._log_base += len(self._log)
            self._log.clear()
            self._prio_log_base += len(self._prio_log)
            self._prio_log.clear()
            self._votes_bytes = 0
            self.cache.reset()
