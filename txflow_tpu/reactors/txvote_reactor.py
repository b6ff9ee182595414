"""TxVote reactor: sign mempool txs + gossip the vote pool (channel 0x32).

Reference: txvotepool/reactor.go. Two duties, preserved:

- ``signTxRoutine`` (:87-138): walk the mempool; if this node's key is in
  the current validator set, sign a TxVote per tx at the state's last
  block height and inject it into the local vote pool.
- per-peer broadcast (:198-265): walk the vote pool from a stable cursor,
  suppress votes the peer itself sent us (sender ids, :298-359), throttle
  votes more than one height ahead of the peer ("allow for a lag of 1
  block", :240), and ship what remains.

Deviation (TPU-first): votes travel in *batched* frames — the consumer is
a device kernel fed thousands of votes per step; one-vote-per-message
framing (reference :244-247) would bottleneck the host. Frame format:
``msg_type u8 | body``; type 1 body = repeated uvarint-length-prefixed
amino TxVote, type 2 body = uvarint height (peer state update, standing in
for the consensus reactor's PeerState that the reference reads at :233).
"""

from __future__ import annotations

import threading

from ..analysis.lockgraph import make_lock
import time
from dataclasses import dataclass
from typing import Callable

from ..codec import amino
from ..trace.tracer import NULL_TRACER, SPAN_PRE_DROP, SPAN_SIGN, SPAN_SIGN_WAIT
from ..utils.clock import monotonic
from ..p2p.base import CHANNEL_TXVOTE, ChannelDescriptor, Reactor
from ..pool.mempool import (
    LANE_PRIORITY,
    ErrMempoolIsFull,
    ErrTxInCache,
    ErrTxTooLarge,
    Mempool,
    TxInfo,
)
from ..pool.txvotepool import TxVotePool
from ..crypto.hash import sha256
from ..types import TxVote, encode_tx_vote
from ..types.tx_vote import decode_tx_votes_many
from ..utils.cache import LRUMap
from ..types.priv_validator import PrivValidator
from ..types.validator import ValidatorSet

MSG_VOTES = 1
MSG_HEIGHT = 2
_MSG_VOTES_B = bytes([MSG_VOTES])

PEER_CATCHUP_SLEEP = 0.005  # reference peerCatchupSleepIntervalMS=100; faster here
PEER_HEIGHT_KEY = "txvote_height"


@dataclass
class StateView:
    """The slice of node state the reactors read (reference reads
    state.State directly, txvotepool/reactor.go:111-115)."""

    chain_id: str
    last_block_height: int
    validators: ValidatorSet
    # committee mode (committee/): the epoch's sampled tx-vote committee
    # for votes at last_block_height. None = full-set mode — every
    # validator signs, no committee pre-check.
    committee: ValidatorSet | None = None


def encode_vote_batch(votes: list[TxVote]) -> bytes:
    body = bytearray([MSG_VOTES])
    for v in votes:
        body += amino.length_prefixed(encode_tx_vote(v))
    return bytes(body)


class TxVoteReactor(Reactor):
    # process-wide decoded-vote cache (see __init__ comment)
    _shared_wire = LRUMap(1 << 16)

    def __init__(
        self,
        get_state: Callable[[], StateView],
        mempool: Mempool,
        tx_vote_pool: TxVotePool,
        priv_val: PrivValidator | None = None,
        broadcast: bool = True,
        batch_size: int = 1024,
        poll_interval: float = 0.05,
        regossip_interval: float | None = None,
    ):
        super().__init__("txvote")
        self.get_state = get_state
        self.mempool = mempool
        self.tx_vote_pool = tx_vote_pool
        self.priv_val = priv_val
        self.broadcast = broadcast
        self.batch_size = batch_size
        self.poll_interval = poll_interval
        # anti-entropy for lossy links (faults.chaos): the cursor walk
        # ships each pool entry to each peer exactly once, so a frame lost
        # in transit is never offered to that peer again. When set, an
        # idle broadcast routine re-walks the live pool every interval;
        # receivers dedup re-offers cheaply (wire cache + pool signature
        # dedup). None (default) keeps the single-pass walk — in-memory
        # pipes don't lose frames, and the re-walk is pure overhead there.
        self.regossip_interval = regossip_interval
        # per-tx tracing (trace/tracer.py): the sign walk records a
        # sign_walk span per sampled tx; wired by the node
        self.tracer = NULL_TRACER
        # accountable gossip (health/byzantine.py, wired by the node):
        # quarantine gate + O(1) pre-check drop accounting. None = every
        # check below short-circuits to the pre-ledger behavior.
        self.ledger = None
        self._running = threading.Event()
        self._peer_ids: dict[str, int] = {}  # node_id -> small int (txVotePoolIDs)
        self._next_peer_id = 1
        self._ids_mtx = make_lock("reactors.TxVoteReactor._ids_mtx")
        self._threads: list[threading.Thread] = []
        self._sign_thread: threading.Thread | None = None
        # wire-segment dedup + decoded-vote sharing: raw segment bytes ->
        # (pool vote key, decoded TxVote). Gossip delivers each vote ~2-3x
        # (independent forwarders) and, with co-located nodes, N nodes
        # each decode the SAME canonical bytes (~10 us each, r3/r4
        # profiles). Canonical wire caching makes all forwarders emit
        # identical bytes, so the raw segment IS the key; the map is
        # PROCESS-WIDE (class attribute) so the first node to decode a
        # vote shares the immutable object with every other node —
        # nothing downstream mutates pooled votes, and the key binds the
        # exact bytes, so a hostile variant encoding simply misses and
        # pays its own decode. Sender bookkeeping stays per-node in the
        # pool; the pool's signature dedup remains authoritative.
        self._seen_wire = TxVoteReactor._shared_wire

    # -- channels --

    def get_channels(self) -> list[ChannelDescriptor]:
        # priority 5, like the reference (txvotepool/reactor.go:142-149)
        return [ChannelDescriptor(id=CHANNEL_TXVOTE, priority=5)]

    # -- lifecycle --

    def on_start(self) -> None:
        self._running.set()
        self._sign_thread = threading.Thread(
            target=self._sign_tx_routine, name="txvote-sign", daemon=True
        )
        self._sign_thread.start()

    def on_stop(self) -> None:
        self._running.clear()
        if self._sign_thread is not None:
            self._sign_thread.join(timeout=2)
            self._sign_thread = None
        for t in self._threads:
            t.join(timeout=2)
        self._threads = []

    # -- peer management --

    def _peer_id(self, peer) -> int:
        with self._ids_mtx:
            pid = self._peer_ids.get(peer.node_id)
            if pid is None:
                pid = self._next_peer_id
                self._next_peer_id += 1
                self._peer_ids[peer.node_id] = pid
                if self.ledger is not None:
                    # bind the pool sender id to the peer's node_id so
                    # engine-side verdict attribution reaches the
                    # scoreboard (which keys on node ids)
                    self.ledger.register_peer(pid, peer.node_id)
            return pid

    def add_peer(self, peer) -> None:
        self._peer_id(peer)  # reserve (reference ids.ReserveForPeer)
        # tell the peer our height so its lag throttle tracks us
        st = self.get_state()
        peer.try_send(
            CHANNEL_TXVOTE,
            bytes([MSG_HEIGHT]) + amino.uvarint(max(st.last_block_height, 0)),
        )
        if self.broadcast:
            t = threading.Thread(
                target=self._broadcast_routine,
                args=(peer,),
                name=f"txvote-bcast-{peer.node_id}",
                daemon=True,
            )
            self._threads.append(t)
            t.start()

    def remove_peer(self, peer, reason: object = None) -> None:
        # broadcast routine exits on peer.is_running(); id mapping kept so a
        # reconnecting peer reuses its slot (reclaim is a no-op here)
        pass

    # -- receive (reference :170-190) --

    def receive(self, chan_id: int, peer, msg: bytes) -> None:
        if not msg:
            raise ValueError("empty txvote message")
        msg_type = msg[0]
        if msg_type == MSG_VOTES:
            ledger = self.ledger
            if ledger is not None and ledger.quarantined(peer.node_id):
                # circuit breaker tripped for this peer: drop the whole
                # frame at the front door. The uvarint skip-walk counts
                # segments without decoding a single vote — a flooding
                # peer costs O(frame bytes) here, never a device slot.
                n = 0
                r = amino.AminoReader(msg, 1)
                while not r.eof():
                    r.read_bytes()
                    n += 1
                ledger.note_frame(peer.node_id, 0, {"quarantined": n})
                return
            pid = self._peer_id(peer)
            r = amino.AminoReader(msg, 1)
            pool = self.tx_vote_pool
            seen = self._seen_wire
            tx_info = TxInfo(sender_id=pid)
            ingest: list = []  # (wk, vote) needing the authoritative path
            fresh_segs: list[bytes] = []  # wire-cache misses: batch decode
            fresh_slots: list[int] = []  # their ingest positions
            n_replayed = 0  # same-peer identical re-sends (ledger window)
            while not r.eof():
                seg = r.read_bytes()  # decode error -> peer stopped
                # raw seg bytes ARE the cache key: siphash of ~150 B costs
                # ~1/4 of a sha256, and peek() reads without the map lock
                # (r5 profile: 12 receive threads contended one lock)
                wk = seg
                hit = seen.peek(wk)
                if hit is not None:
                    vk, vote = hit
                    code = pool.add_sender(vk, pid)
                    if code:
                        # dup AND the pool still holds it: nothing to do
                        # beyond the peer's dup counter (health scoring —
                        # legit gossip redundancy is discounted there).
                        # SENDER_REPEAT = THIS peer already delivered this
                        # exact signature: counted for the ledger's replay
                        # accounting (an honest watchdog re-offer or a
                        # replay flood — the breaker's opt-in rate
                        # threshold tells them apart).
                        # If the pool dropped it (purge/flush/eviction),
                        # fall through to the authoritative check_tx path
                        # — the wire cache must never overrule the pool's
                        # own re-accept policy (r3 review finding) — but
                        # reuse the shared decoded object either way.
                        if code == TxVotePool.SENDER_REPEAT:
                            n_replayed += 1
                        peer.stats.duplicates += 1
                        continue
                    if pool.in_cache(vk):
                        # pool dropped it but its dedup cache still vetoes
                        # re-entry (committed/purged vote being re-
                        # gossiped): check_tx would reject with
                        # ErrTxInCache and no side effects (the entry is
                        # gone, so there is no sender set to update) —
                        # skip the authoritative round trip entirely
                        peer.stats.duplicates += 1
                        continue
                    ingest.append((wk, vote))
                else:
                    # placeholder keeps WIRE order: acceptance at the
                    # pool-full boundary must see votes in arrival order,
                    # not hits-then-misses (r5 review)
                    fresh_slots.append(len(ingest))
                    ingest.append((wk, None))
                    fresh_segs.append(seg)
            if fresh_segs:
                # one C field-walk for the whole frame's unknown segs
                # (decode error -> ValueError -> peer stopped, same as
                # the per-seg decoder)
                for slot, vote in zip(
                    fresh_slots, decode_tx_votes_many(fresh_segs)
                ):
                    ingest[slot] = (ingest[slot][0], vote)
            n_unknown = n_stale = n_noncomm = 0
            if ingest and ledger is not None:
                # O(1)-per-vote pre-checks, BEFORE the pool and the
                # device: a vote from a signer outside the validator set
                # can never reach quorum, and a vote far below our height
                # is either ancient re-gossip or a stale-flood — both die
                # here, order-preserving for everything kept (honest
                # certificate parity). Pre-dropped segs deliberately do
                # NOT enter the wire cache: each re-delivery is re-judged
                # and re-counted against the sender.
                st = self.get_state()
                vals = st.validators
                committee = st.committee
                min_height = st.last_block_height - ledger.cfg.stale_height_slack
                kept = []
                tr = self.tracer
                for wk, vote in ingest:
                    if not vals.has_address(vote.validator_address):
                        n_unknown += 1
                    elif vote.height < min_height:
                        n_stale += 1
                    elif (
                        committee is not None
                        and vote.height == st.last_block_height
                        and not committee.has_address(vote.validator_address)
                    ):
                        # committee mode: a real validator signing a
                        # current-height tx vote from OUTSIDE the epoch's
                        # sampled committee can never reach committee
                        # quorum — O(1) drop before the pool and device.
                        # Gated on exact height: a vote straddling an
                        # epoch boundary belongs to another epoch's
                        # committee and is left to the tally to judge.
                        n_noncomm += 1
                    else:
                        kept.append((wk, vote))
                        continue
                    if tr.active and tr.sampled(vote.tx_hash):
                        t = monotonic()
                        tr.span(vote.tx_hash, SPAN_PRE_DROP, t, t)
                ingest = kept
            if ingest:
                # one pool lock for the whole frame (check_tx_many);
                # full/too-large rejections drop the vote like the
                # reference, in-cache dups still enter the wire cache
                errs = pool.check_tx_many(
                    [v for _, v in ingest], tx_info
                )
                for (wk, vote), err in zip(ingest, errs):
                    if err is None or isinstance(err, ErrTxInCache):
                        seen.put(wk, (vote.vote_key(), vote))
                    if err is not None and isinstance(err, ErrTxInCache):
                        peer.stats.duplicates += 1
            if ledger is not None and (
                ingest or n_unknown or n_stale or n_noncomm or n_replayed
            ):
                drops = {}
                if n_unknown:
                    drops["unknown_validator"] = n_unknown
                if n_stale:
                    drops["stale_height"] = n_stale
                if n_noncomm:
                    drops["non_committee"] = n_noncomm
                if n_replayed:
                    drops["replayed_sig"] = n_replayed
                ledger.note_frame(peer.node_id, len(ingest), drops or None)
        elif msg_type == MSG_HEIGHT:
            height, _ = amino.read_uvarint(msg, 1)
            peer.set(PEER_HEIGHT_KEY, height)
        else:
            raise ValueError(f"unknown txvote msg type {msg_type}")

    def broadcast_height(self, height: int) -> None:
        """Push a height update to all peers (block-boundary hook)."""
        if self.switch is not None:
            self.switch.broadcast(
                CHANNEL_TXVOTE, bytes([MSG_HEIGHT]) + amino.uvarint(max(height, 0))
            )

    # -- sign routine (reference :87-138) --

    def _sign_tx_routine(self) -> None:
        cursor = 0
        pcursor = 0
        seq = self.mempool.seq()
        while self._running.is_set():
            # drain the priority lane first each pass: under overload the
            # bulk walk can be arbitrarily deep, and priority txs must
            # reach quorum at a flat latency regardless (ISSUE 6)
            pitems, pcursor = self.mempool.priority_entries_from(
                pcursor, limit=self.batch_size
            )
            items, cursor = self.mempool.entries_from(cursor, limit=self.batch_size)
            items = pitems + [it for it in items if it[4] != LANE_PRIORITY]
            if not items:
                seq = self.mempool.wait_for_new(seq, timeout=self.poll_interval)
                continue
            st = self.get_state()
            if self.priv_val is None:
                continue
            my_addr = self.priv_val.get_address()
            if not st.validators.has_address(my_addr):
                continue  # keep running: could become a validator any round
            if st.committee is not None and not st.committee.has_address(my_addr):
                # committee mode: only committee members sign tx votes —
                # this is WHERE the gossip savings come from (votes per tx
                # = committee size, not validator count). Keep running:
                # the next epoch's sample may include us.
                continue
            tr = self.tracer
            for tx_key, tx, _h, fast_path, _lane in items:
                if not fast_path:
                    # app flagged this tx block-only (e.g. EndBlock-
                    # coupled validator updates): honest validators do
                    # not sign it, so no fast-path quorum can form and
                    # the block path carries it
                    continue
                traced = tr.active and tr.sampled_key(tx_key)
                t0 = monotonic() if traced else 0.0
                # the mempool key IS sha256(tx) — no recompute
                vote = TxVote(
                    height=st.last_block_height,
                    tx_hash=tx_key.hex().upper(),
                    tx_key=tx_key,
                    validator_address=my_addr,
                )
                self.priv_val.sign_tx_vote(st.chain_id, vote)
                try:
                    self.tx_vote_pool.check_tx(vote)
                except (ErrTxInCache, ErrMempoolIsFull, ErrTxTooLarge):
                    continue
                if traced:
                    # sign_wait: mempool insert (where a sampled tx is
                    # anchored) -> this walk took the tx up
                    t_in = tr.anchored(vote.tx_hash)
                    if t_in is not None:
                        tr.span(vote.tx_hash, SPAN_SIGN_WAIT, t_in, t0)
                    tr.span(vote.tx_hash, SPAN_SIGN, t0, monotonic())

    # -- per-peer broadcast (reference :198-265) --

    def _broadcast_routine(self, peer) -> None:
        pid = self._peer_id(peer)
        cursor = 0
        pending: list[tuple[bytes, TxVote, int, bytes]] = []
        seq = self.tx_vote_pool.seq()
        last_rewalk = monotonic()
        while self._running.is_set() and peer.is_running():
            if not pending:
                pending, cursor = self.tx_vote_pool.entries_from(
                    cursor, limit=self.batch_size
                )
            if not pending:
                if (
                    self.regossip_interval is not None
                    and monotonic() - last_rewalk >= self.regossip_interval
                    and self.tx_vote_pool.size() > 0
                ):
                    cursor = 0  # anti-entropy re-walk (see __init__)
                    last_rewalk = monotonic()
                    continue
                seq = self.tx_vote_pool.wait_for_new(seq, timeout=self.poll_interval)
                continue
            peer_height = peer.get(PEER_HEIGHT_KEY, 0)
            known = self.tx_vote_pool.has_sender_many(
                [key for key, _v, _h, _s in pending], pid
            )
            sendable, deferred = [], []
            for (key, vote, _h, seg), peer_has in zip(pending, known):
                if vote.height - 1 > peer_height:  # allow a lag of 1 block
                    deferred.append((key, vote, _h, seg))
                elif not peer_has:
                    sendable.append(seg)
            if sendable:
                # the frame is a join of ingest-time cached segments: the
                # per-peer walk never re-serializes a vote (r4 profile)
                frame = _MSG_VOTES_B + b"".join(sendable)
                if not peer.send(CHANNEL_TXVOTE, frame):
                    time.sleep(PEER_CATCHUP_SLEEP)
                    continue  # retry the same batch
            pending = deferred
            if deferred:
                time.sleep(PEER_CATCHUP_SLEEP)  # peer catching up
