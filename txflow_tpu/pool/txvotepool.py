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

from ..trace.tracer import NULL_TRACER, SPAN_VOTE_INGEST
from ..types import TxVote, decode_tx_vote
from ..types.tx_vote import ingest_bytes_many
from ..utils.cache import make_lru
from ..utils.clock import monotonic, thread_time
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


# A resident vote's record is ONE tuple, the only object the collector
# tracks for it besides the TxVote itself (a quarter of a million votes are
# resident under a flood, and every young collection walks what the ingest
# allocated): ``(vote, height, seg, senders, lane)``, read by index.
#
# height: the pool's height at ingest.
# seg: the uvarint-length-prefixed wire form, built once at ingest: the
#   gossip batch frame is a plain b"".join of these, so per-peer broadcast
#   walks never re-serialize (r4 profile: lp+append per vote per peer). The
#   vote's encoded size, which the byte accounting adds at ingest and takes
#   off at removal, is this segment's payload (_wire_size): never stored,
#   never re-encoded.
# senders: the ONE sender id until a second peer delivers the vote, then a
#   tuple of ids in order of arrival. Its first element (or the id itself)
#   is the ORIGIN: the sender whose delivery created this entry, frozen at
#   ingest. Invalid-signature verdicts are attributed to the origin, not
#   the whole sender set — later duplicate senders never cost a device
#   slot, and striking them would punish honest gossip redundancy
#   (health/byzantine.py). UNKNOWN_PEER_ID = local/RPC/WAL ingest: no peer
#   to strike.
# lane: ingest-time admission lane (LANE_PRIORITY or -1): the partition
#   key for the engine's lane-split drain. Frozen at ingest so the
#   priority log and bulk_entries_from stay an exact partition of the main
#   log even if the lane hook's answer drifts later (mempool eviction,
#   late tx arrival) — a vote is delivered by EXACTLY the log its ingest
#   classified it into.
_VOTE, _HEIGHT, _SEG, _SENDERS, _LANE = range(5)
_Record = tuple  # (TxVote, int, bytes, int | tuple[int, ...], int)


def _wire_size(seg: bytes) -> int:
    """Payload length of a length-prefixed segment: the vote's wire size."""
    n = len(seg)
    if n <= 128:  # a one-byte prefix: payloads under 128
        return n - 1
    if n <= 16385:  # two bytes: payloads under 16,384, every real vote
        return n - 2
    k = 3
    while n - k >= 1 << (7 * k):
        k += 1
    return n - k


def _has_sender(senders, sender_id: int) -> bool:
    if type(senders) is tuple:
        return sender_id in senders
    return senders == sender_id


class TxVotePool(IngestLogPool):
    def __init__(self, config: MempoolConfig, height: int = 0, wal_path: str = ""):
        super().__init__()  # _mtx/_cond/_seq + compacted ingest log
        self.config = config
        self.height = height
        self._votes: dict[bytes, _Record] = self._items  # vote_key -> record
        # secondary index: tx_hash -> {vote_key: None} (an insertion-
        # ordered set), so segs_for_tx is O(votes-for-tx) instead of a
        # full O(pool) scan — the quorum-stall watchdog calls it per
        # stalled tx, and at bench depth the scan was the whole pool.
        # Maintained by the ingest (check_tx_many) and every removal path.
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
        # what the ingest costs and which way a vote's bytes came
        # (types/tx_vote.py ingest_bytes_many): votes offered, the thread
        # CPU seconds of their frames (two clock reads a frame, another
        # thread's hold of the interpreter lock is not in them), and of
        # the votes how many were laid out in one pass (fast), went
        # through encode_tx_vote (general), or arrived with their wire
        # form (primed). Written under _mtx, in a frame's last hold.
        self.ingest_votes = 0
        self.ingest_cpu_s = 0.0
        self.ingest_fast = 0
        self.ingest_general = 0
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

    def ingest_stats(self) -> dict:
        """The ingest's counters, one consistent reading: ``votes``,
        ``cpu_s``, ``fast``, ``general``, ``primed`` (the three ways add
        up to the votes)."""
        with self._mtx:
            votes, fast, general = self.ingest_votes, self.ingest_fast, self.ingest_general
            cpu_s = self.ingest_cpu_s
        return {
            "votes": votes, "cpu_s": round(cpu_s, 6), "fast": fast,
            "general": general, "primed": votes - fast - general,
        }

    def has(self, key: bytes) -> bool:
        with self._mtx:
            return key in self._votes

    def has_sender(self, key: bytes, sender_id: int) -> bool:
        with self._mtx:
            entry = self._votes.get(key)
            return entry is not None and _has_sender(entry[_SENDERS], sender_id)

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
                out.append(
                    entry is not None and _has_sender(entry[_SENDERS], sender_id)
                )
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
            if not self._add_sender_locked(key, entry, sender_id):
                return self.SENDER_REPEAT
            return self.SENDER_ADDED

    def _add_sender_locked(self, key: bytes, entry: _Record, sender_id: int) -> bool:
        """Record one more sender of a resident vote (under _mtx); False
        if it is among them already. The record is replaced in place of
        the dict (its position in the walk order stays), the origin stays
        first."""
        senders = entry[_SENDERS]
        if type(senders) is not tuple:
            if senders == sender_id:
                return False
            senders = (senders,)
        elif sender_id in senders:
            return False
        self._votes[key] = (
            entry[_VOTE], entry[_HEIGHT], entry[_SEG],
            senders + (sender_id,), entry[_LANE],
        )
        return True

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
                if entry is None:
                    out.append(UNKNOWN_PEER_ID)
                else:
                    senders = entry[_SENDERS]
                    out.append(senders[0] if type(senders) is tuple else senders)
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
            if self._lane_quiet(e[_VOTE]) == LANE_PRIORITY:
                continue
            self._votes.pop(k)
            self._votes_bytes -= _wire_size(e[_SEG])
            self._index_discard(k, e)
            self.cache.remove(k)
            return True
        return False

    # -- ingest (reference CheckTx/CheckTxWithInfo :180-261) --

    def check_tx(
        self, vote: TxVote, tx_info: TxInfo | None = None, write_wal: bool = True
    ) -> None:
        """Raises on rejection; returns None when the vote entered the
        pool. A one-vote frame through check_tx_many: one ingest, so the
        two cannot drift."""
        err = self.check_tx_many([vote], tx_info, write_wal)[0]
        if err is not None:
            raise err

    def check_tx_many(
        self,
        votes: list[TxVote],
        tx_info: TxInfo | None = None,
        write_wal: bool = True,
    ) -> list[Exception | None]:
        """The pool's ingest: a frame's per-vote acceptance decisions in
        frame order (errors returned, not raised; built only on actual
        rejection), with bounded lock holds (64-vote groups) and one
        waiter wakeup per group.

        A frame is ingested as a frame. Its bytes — wire form, gossip
        segment, dedup key — come from one pass over it BEFORE the lock
        (types/tx_vote.py ingest_bytes_many; a vote that arrives primed
        costs three reads there), so a lock hold is dict stores and
        accounting: one record tuple a vote, the group's keys appended to
        the ingest log in one extend, the byte count carried in a local.
        Sized with tools/ingest_bench.py (sandbox CPU, shape only;
        val64-flood's frames, 65,536 resident, thread CPU a vote over
        several readings): 4.7-6.6 us cold (no cached bytes), 2.5-3.1
        decoded (wire form only), 1.7-2.4 primed, where PR 32's pair,
        which encoded with twenty small calls under the lock and kept a
        dataclass and a set a vote, cost 7.4-11.0, 3.5-4.7 and 2.0-3.1.
        On the chip's host, a val64-flood window (txvote_ingest_cpu_s
        over txvote_ingest_votes): 12.1 -> 6.2 us a vote (PERF.md
        section 5, PR 34)."""
        n = len(votes)
        if n == 0:
            return []
        t0 = thread_time()
        tx_info = tx_info or TxInfo(UNKNOWN_PEER_ID)
        out: list[Exception | None] = [None] * n
        wires, segs, keys, fast, general = ingest_bytes_many(votes)
        sid = tx_info.sender_id
        cfg = self.config
        size_cap = cfg.size
        bytes_cap = cfg.max_txs_bytes
        max_size = cfg.max_msg_bytes - _MSG_OVERHEAD
        cache_push = self.cache.push
        votes_d = self._votes
        by_tx_d = self._by_tx
        lane_of = self.lane_of_vote
        prio_append = self._prio_log.append
        wal = self.wal if write_wal and not self.wal_degraded else None
        tr = self.tracer
        # bounded lock holds: a whole gossip frame under one lock starved
        # the drain/purge/inject paths for milliseconds (r5 instrumented
        # profile) — 64 votes ≈ a hundred µs, keeping the pool fair
        for base in range(0, n, 64):
            end = min(base + 64, n)
            accepted: list[bytes] = []  # this group's keys, in ingest order
            with self._mtx:
                height = self.height
                nbytes = self._votes_bytes
                tr_active = tr.active
                for i in range(base, end):
                    vote = votes[i]
                    vote_size = len(wires[i])
                    lane = -1
                    if lane_of is not None:
                        try:
                            lane = lane_of(vote)
                        except Exception:
                            lane = -1
                    if len(votes_d) >= size_cap or vote_size + nbytes > bytes_cap:
                        if lane == LANE_PRIORITY:
                            # bulk occupancy must not block a priority vote
                            self._votes_bytes = nbytes
                            while (
                                len(votes_d) >= size_cap
                                or vote_size + self._votes_bytes > bytes_cap
                            ) and self._evict_bulk_locked():
                                pass
                            nbytes = self._votes_bytes
                        if len(votes_d) >= size_cap or vote_size + nbytes > bytes_cap:
                            out[i] = ErrMempoolIsFull(
                                len(votes_d), size_cap, nbytes, bytes_cap
                            )
                            continue
                    if vote_size > max_size:
                        out[i] = ErrTxTooLarge(max_size, vote_size)
                        continue
                    key = keys[i]
                    if not cache_push(key):
                        entry = votes_d.get(key)
                        if entry is not None:
                            self._add_sender_locked(key, entry, sid)
                        out[i] = ErrTxInCache()
                        continue
                    if wal is not None:
                        try:
                            wal.write(wires[i])  # txlint: allow(lock-blocking) -- WAL append order must match ingest-log order; buffered write, fsync only if sync_on_write
                        except (OSError, FailpointError):
                            self.wal_degraded = True
                            self.wal_errors += 1
                            wal = None
                    if not accepted and not self._first_new_t:
                        self._first_new_t = monotonic()
                    votes_d[key] = (vote, height, segs[i], sid, lane)
                    tx_hash = vote.tx_hash
                    by_tx = by_tx_d.get(tx_hash)
                    if by_tx is None:
                        by_tx = by_tx_d[tx_hash] = {}
                    by_tx[key] = None
                    accepted.append(key)
                    if lane == LANE_PRIORITY:
                        prio_append(key)
                    nbytes += vote_size
                    if tr_active and tr.sampled(tx_hash):
                        t = monotonic()
                        tr.span(tx_hash, SPAN_VOTE_INGEST, t, t)
                        tr.first_vote(tx_hash, t)
                self._votes_bytes = nbytes
                if end == n:
                    # in the frame's last hold: a hold of their own, taken
                    # right after the waiters were woken, cost val4-served
                    # 7% of its p95 (PERF.md section 6, PR 33)
                    self.ingest_votes += n
                    self.ingest_fast += fast
                    self.ingest_general += general
                    self.ingest_cpu_s += thread_time() - t0
                if accepted:  # an all-dup group must not wake consumers
                    self._log_extend_quiet(accepted)
                    self._log_notify()
                    self._notify_txs_available()
        return out

    def _notify_txs_available(self) -> None:
        if self._notify_available and not self._notified_txs_available:
            self._notified_txs_available = True
            self._txs_available.set()

    # -- consumption --

    def reap_max_txs(self, max_: int) -> list[TxVote]:
        with self._mtx:
            if max_ < 0:
                max_ = len(self._votes)
            return [e[_VOTE] for e in list(self._votes.values())[:max_]]

    def drain_batch(self, max_: int, skip: set[bytes] | None = None) -> list[tuple[bytes, TxVote]]:
        """Snapshot up to max_ (key, vote) pairs in order, skipping keys."""
        out = []
        with self._mtx:
            for k, e in self._votes.items():
                if skip is not None and k in skip:
                    continue
                out.append((k, e[_VOTE]))
                if len(out) >= max_:
                    break
        return out

    def entries(self, after: int = 0, limit: int = -1) -> list[tuple[bytes, TxVote]]:
        """Snapshot of (key, vote) pairs in insertion order (gossip walk)."""
        with self._mtx:
            items = [(k, e[_VOTE]) for k, e in self._votes.items()]
        if limit >= 0:
            return items[after : after + limit]
        return items[after:]

    def entries_from(
        self, cursor: int, limit: int = 256
    ) -> tuple[list[tuple[bytes, TxVote, int, bytes]], int]:
        """Stable-cursor walk of live votes: (key, vote, height, wire seg)
        tuples; see IngestLogPool._entries_from for the cursor contract."""
        raw, pos = self._entries_from(cursor, limit)
        return [(k, e[_VOTE], e[_HEIGHT], e[_SEG]) for k, e in raw], pos

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
                if e is not None and e[_LANE] != LANE_PRIORITY:
                    out.append((key, e[_VOTE], e[_HEIGHT], e[_SEG]))
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
                    out.append((key, e[_VOTE], e[_HEIGHT], e[_SEG]))
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
                    out.append(entry[_SEG])
                    if len(out) >= limit:
                        break
        return out

    def _index_discard(self, k: bytes, entry: _Record) -> None:
        """Drop one key from the per-tx index (under self._mtx)."""
        tx_hash = entry[_VOTE].tx_hash
        by_tx = self._by_tx.get(tx_hash)
        if by_tx is not None:
            by_tx.pop(k, None)
            if not by_tx:
                del self._by_tx[tx_hash]

    def remove(self, keys: list[bytes], cache_too: bool = False) -> None:
        """Remove votes by key (quorum purge path)."""
        with self._mtx:
            for k in keys:
                entry = self._votes.pop(k, None)
                if entry is not None:
                    self._votes_bytes -= _wire_size(entry[_SEG])
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
                    self._votes_bytes -= _wire_size(entry[_SEG])
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
