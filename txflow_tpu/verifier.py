"""VoteVerifier: the interface between protocol logic and the verify/tally kernel.

The reference verifies one vote at a time inside ``TxVoteSet.AddVote``
(reference types/vote_set.go:117-119 -> types/tx_vote.go:110-119), serialized
through one goroutine (txflow/service.go:123-166). Here the same decision —
"is this signature valid, and does the tx now have >2/3 stake" — is computed
for a whole batch of in-flight (tx, validator) votes at once:

- ``ScalarVoteVerifier``  — the golden model: host ed25519 (audited port of
  Go crypto/ed25519 semantics) + int64 stake accumulation. Slow, correct,
  and the parity oracle for every other implementation.
- ``DeviceVoteVerifier``  — batched JAX kernel (ops.ed25519_batch +
  ops.tally), bucketed padding so in-flight count variation does not cause
  recompilation storms, optional shard_map over a device mesh with the
  stake tally psum-combined over ICI (parallel.mesh).

Both return bit-identical accept/reject masks and quorum decisions; the
engine (engine.txflow) feeds accepted votes into the authoritative host
``TxVoteSet`` so duplicate/conflict bookkeeping stays first-signature-wins
exactly like the reference.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np

from .analysis.lockgraph import make_lock, note_blocking
from .analysis.racegraph import shared_field
from .crypto import ed25519 as host_ed
from .ops import ed25519_batch, tally
from .types.validator import ValidatorSet
from .utils.clock import monotonic

# Batch-size buckets: in-flight vote counts vary wildly (SURVEY.md §7 hard
# part 4); padding to the next bucket keeps the number of distinct compiled
# shapes small and bounded.
DEFAULT_BUCKETS = (64, 256, 1024, 4096, 16384, 65536)


def bucket_size(n: int, buckets=DEFAULT_BUCKETS, multiple: int = 1) -> int:
    """Smallest bucket >= n after rounding buckets up to `multiple`.

    Each ladder rung is rounded up for mesh divisibility BEFORE the
    comparison, so a non-power-of-two mesh (e.g. 6 devices) still yields
    one stable shape per bucket instead of a fresh shape per batch size —
    and a drain sized exactly at a rounded rung (the coalescer's
    shard-rounded full-bucket targets) pads zero instead of spilling to
    the next rung up.
    """
    for b in buckets:
        bb = ((b + multiple - 1) // multiple) * multiple
        if bb >= n:
            return bb
    # beyond the largest bucket: round up to a multiple
    return ((n + multiple - 1) // multiple) * multiple


class VerifyCache:
    """Cross-engine signature-verification result cache.

    Verification is a pure function of (message, signature, public key):
    when several engines are co-located in one process (LocalNet; several
    validators on one host sharing one chip), full-mesh gossip hands every
    engine the same votes, and each engine re-verifying them multiplies
    the device work by the engine count for zero information (measured r4:
    the 4-node bench ran 4x the kernel work of the 1-node case). The first
    engine to see a vote pays the device verify; the rest hit this cache.

    Keys bind ALL inputs — sha256(len(msg) ‖ msg ‖ len(sig) ‖ sig ‖
    pubkey) — so a byzantine validator re-using one signature across
    different payloads can never alias a cached verdict, and (r4 advisor)
    the key survives validator-set changes: it binds the *resolved public
    key*, not the validator index, so a cache outliving an END_BLOCK
    validator update can never replay a verdict against a different key
    that now occupies the same index. Fields are length-prefixed so no
    (msg, sig) split ambiguity exists either. The reference has no
    analog: its validators are one-process-per-node, so the question
    never arises (txflow/service.go:123-166 verifies serially per node).
    """

    def __init__(self, capacity: int = 1 << 17, claim_ttl: float = 3.0):
        import threading
        from collections import OrderedDict

        self.capacity = capacity
        self.claim_ttl = claim_ttl
        self._mtx = make_lock("verifier.VerifyCache._mtx")
        # verdicts + in-flight claims: every co-located engine's verify
        # path races through these tables
        self._sh_claims = shared_field("verifier.VerifyCache.claims")  # txlint: shared(self._mtx)
        self._d: OrderedDict[bytes, bool] = OrderedDict()
        # in-flight claims: key -> monotonic claim time. Without claims,
        # co-located engines that miss on the SAME votes all ship them to
        # the device in the same beat — N redundant verifies AND (worse,
        # measured r5 on TPU: 580 votes/s vs 12k without the cache) each
        # engine pays a full padded device call for its tiny private miss
        # set. A claim hands each vote to exactly one engine; the others
        # defer the vote to their next step, by which time it is a hit.
        self._inflight: dict[bytes, float] = {}
        self.hits = 0
        self.misses = 0
        self.deferrals = 0

    @staticmethod
    def key(msg: bytes, sig: bytes, pub_key: bytes) -> bytes:
        from .crypto.hash import sha256

        return sha256(
            len(msg).to_bytes(4, "little")
            + msg
            + len(sig).to_bytes(4, "little")
            + sig
            + pub_key
        )

    def lookup_or_claim_many(
        self, keys: list[bytes | None]
    ) -> tuple[list[bool | None], np.ndarray]:
        """One lock hold: resolve hits, CLAIM unclaimed misses for this
        caller, and flag misses already in flight elsewhere.

        Returns (vals, pending): vals[i] is the cached verdict or None for
        a miss; pending[i] is True when the miss is owned by another
        caller — the caller must NOT verify it (defer/re-offer instead)
        and None-vals with pending False are claimed by THIS caller, which
        must eventually store_many or release_many them. Claims older than
        claim_ttl are treated as abandoned (owner died mid-verify) and
        handed to the next asker.
        """
        n = len(keys)
        vals: list[bool | None] = [None] * n
        pending = np.zeros(n, dtype=bool)
        now = time.monotonic()
        stale = now - self.claim_ttl
        with self._mtx:
            self._sh_claims.note_write()
            d = self._d
            infl = self._inflight
            for i, k in enumerate(keys):
                if k is None:
                    continue
                v = d.get(k)
                if v is not None:
                    d.move_to_end(k)
                    vals[i] = v
                    self.hits += 1
                    continue
                t = infl.get(k)
                if t is not None and t > stale:
                    # another caller's verify is in flight: a deferral,
                    # not a miss — misses counts actual claimed verifies
                    pending[i] = True
                    self.deferrals += 1
                else:
                    self.misses += 1
                    infl[k] = now  # claimed by this caller
        return vals, pending

    def release_many(self, keys: list[bytes]) -> None:
        """Drop claims without storing results (verify failed/raised)."""
        with self._mtx:
            self._sh_claims.note_write()
            for k in keys:
                self._inflight.pop(k, None)

    def store_many(self, pairs: list[tuple[bytes, bool]]) -> None:
        with self._mtx:
            self._sh_claims.note_write()
            d = self._d
            infl = self._inflight
            for k, v in pairs:
                d[k] = v
                d.move_to_end(k)
                infl.pop(k, None)
            while len(d) > self.capacity:
                d.popitem(last=False)

    def heartbeat_many(self, keys: list[bytes]) -> None:
        """Re-stamp still-live claims: the owner's verify call is in
        flight but slow. Claims already released/stored are left alone."""
        now = time.monotonic()
        with self._mtx:
            self._sh_claims.note_write()
            infl = self._inflight
            for k in keys:
                if k in infl:
                    infl[k] = now

    def claim_keepalive(self, keys: list[bytes]) -> "_ClaimKeepalive":
        """Context manager that heartbeats the given claims every
        claim_ttl/2 until exit. The TTL (3 s) is sized for a warm verify
        step, but the owner's device call can exceed it by orders of
        magnitude — a cold-shape compile runs minutes on TPU — and once a
        claim goes stale every other engine re-claims the same votes and
        launches its own compile of the same cold shape (N concurrent
        compiles for one shape). The heartbeat keeps ownership exactly as
        long as the owner is actually working."""
        return _ClaimKeepalive(self, keys)


class _ClaimKeepalive:
    """Background heartbeat for VerifyCache claims (claim_keepalive)."""

    def __init__(self, cache: VerifyCache, keys: list[bytes]):
        self._cache = cache
        self._keys = keys
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def __enter__(self) -> "_ClaimKeepalive":
        if self._keys:
            self._thread = threading.Thread(
                target=self._run, name="verify-claim-keepalive", daemon=True
            )
            self._thread.start()
        return self

    def _run(self) -> None:
        interval = max(self._cache.claim_ttl / 2, 0.01)
        # first beat immediately: the claims were stamped at lookup time,
        # possibly a while before this thread got scheduled — with a short
        # TTL (tests, aggressive configs) waiting a full interval first
        # leaves a window where the claims are already re-claimable
        self._cache.heartbeat_many(self._keys)
        while not self._stop.wait(interval):
            self._cache.heartbeat_many(self._keys)

    def __exit__(self, *exc) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)
            self._thread = None


@dataclass
class TallyResult:
    """Outcome of one verify+tally step over a vote batch."""

    valid: np.ndarray  # bool[B]  per-vote signature validity (False for dropped)
    stake: np.ndarray  # int[n_slots] cumulative stake per tx slot (incl. prior)
    maj23: np.ndarray  # bool[n_slots] quorum reached (latched via prior stake)
    dropped: np.ndarray  # bool[B] in-batch (slot, validator) repeat: not processed


class VerifyTicket:
    """Handle to an in-flight verify+tally call (submit/collect split).

    ``submit(...)`` dispatches the work — for the device verifier that
    means the XLA program is launched but the ``np.asarray`` readback has
    NOT been forced, so host code (batch prep for the next drain, commit
    routing for the previous one) runs while the device computes.
    ``result()`` blocks for the readback and returns the ``TallyResult``;
    it may be called exactly once per ticket from any thread, and any
    cache claims the call took are settled (stored or released) by the
    time it returns or raises — a ticket never leaks claims.

    ``ready_t`` is the moment (utils.clock monotonic) the result was
    usable on the host, where the ticket can tell: the staging ring's
    thread stamps it when its readback has returned AND the thread holds
    the interpreter lock again (under load that is later than the device
    finished, by the lock wait), and wrapping tickets hand it through.
    None = not stamped; the engine then takes the end of its own
    ``result()`` call (engine/txflow.py ``_collect``, the device_busy
    span's end).
    """

    ready_t: float | None = None

    def result(self) -> TallyResult:
        raise NotImplementedError


class ReadyTicket(VerifyTicket):
    """Already-completed ticket: eager paths (scalar verifier, fallbacks)
    present the same submit/collect surface with the work done inline."""

    __slots__ = ("_result", "ready_t")

    def __init__(self, result: TallyResult):
        self._result = result
        self.ready_t = monotonic()  # the work ran inline: ready as it is built

    def result(self) -> TallyResult:
        return self._result


class _RingHandle:
    """A dispatched device array whose readback rides the staging ring.

    Stands in for the raw device array inside tickets: the ring's side
    thread is (or soon will be) pulling the bytes to host, and ``get()``
    waits on that slot with overlap accounting instead of issuing the
    transfer itself."""

    __slots__ = ("_ring", "_slot")

    def __init__(self, ring, slot):
        self._ring = ring
        self._slot = slot

    def get(self) -> np.ndarray:
        return self._ring.result(self._slot)

    @property
    def ready_t(self) -> float | None:
        return self._slot.ready_t


def _force_readback(packed) -> np.ndarray:
    """The ONE blocking device->host readback, ring-aware: staged handles
    wait on their slot (transfer already in flight off-thread), raw
    device arrays take the historical synchronous np.asarray. Same device
    array, same host bytes either way — certificate parity is untouched
    by WHERE the transfer runs."""
    if isinstance(packed, _RingHandle):
        return packed.get()
    return np.asarray(packed)


class _FusedDeviceTicket(VerifyTicket):
    """Dispatched fused kernel (no cache): readback + unpack at result()."""

    __slots__ = ("_packed", "_n", "_n_slots", "_n_shards", "_b", "_b_slots",
                 "_keep", "_done", "ready_t")

    def __init__(self, packed, n, n_slots, n_shards, b, b_slots, keep):
        self._packed = packed  # device array, readback not yet forced
        self._n = n
        self._n_slots = n_slots
        self._n_shards = n_shards
        self._b = b
        self._b_slots = b_slots
        self._keep = keep
        self._done: TallyResult | None = None
        self.ready_t = None

    def result(self) -> TallyResult:
        if self._done is not None:
            return self._done
        note_blocking("verifier.device-readback")
        packed = _force_readback(self._packed)  # the ONE blocking readback
        self.ready_t = getattr(self._packed, "ready_t", None)  # ring-staged only
        self._packed = None
        rows = packed.reshape(self._n_shards, -1)
        bs = self._b // self._n_shards
        valid = rows[:, :bs].reshape(-1).astype(bool)
        stake = rows[0, bs : bs + self._b_slots]
        maj23 = rows[0, bs + self._b_slots :].astype(bool)
        self._done = TallyResult(
            valid[: self._n],
            stake[: self._n_slots].astype(np.int64),
            maj23[: self._n_slots],
            ~self._keep,
        )
        return self._done


class _CachedDeviceTicket(VerifyTicket):
    """Dispatched miss-set verify (cache path): the caller's claims stay
    held (keepalive running) until result() stores or releases them."""

    __slots__ = ("_cache", "_packed", "_keepalive", "_miss_idx", "_miss_keys",
                 "_keys", "_valid", "_tx_slot", "_n_slots", "_prior",
                 "_quorum", "_keep", "_pending", "_powers", "_val_idx",
                 "_n_shards", "_b", "_done", "ready_t")

    def __init__(self, cache, packed, keepalive, miss_idx, miss_keys, keys,
                 valid, tx_slot, n_slots, prior, quorum, keep, pending,
                 powers, val_idx, n_shards, b):
        self._cache = cache
        self._packed = packed
        self._keepalive = keepalive
        self._miss_idx = miss_idx
        self._miss_keys = miss_keys
        self._keys = keys
        self._valid = valid
        self._tx_slot = tx_slot
        self._n_slots = n_slots
        self._prior = prior
        self._quorum = quorum
        self._keep = keep
        self._pending = pending
        self._powers = powers
        self._val_idx = val_idx
        self._n_shards = n_shards
        self._b = b
        self._done: TallyResult | None = None
        self.ready_t = None

    def result(self) -> TallyResult:
        if self._done is not None:
            return self._done
        # re-stamp the claims from the collecting thread before blocking:
        # the keepalive thread normally covers this window, but the
        # readback can start arbitrarily long after dispatch (pipelined
        # engine) and a missed keepalive beat must not cost ownership
        self._cache.heartbeat_many(self._miss_keys)
        note_blocking("verifier.device-readback")
        try:
            packed = _force_readback(self._packed)  # blocking readback
        except BaseException:
            # claims must not outlive a failed readback (waiters would
            # stall until the TTL) — hand them to the next asker
            self._keepalive.__exit__(None, None, None)
            self._cache.release_many(self._miss_keys)
            raise
        self.ready_t = getattr(self._packed, "ready_t", None)  # ring-staged only
        self._packed = None
        self._keepalive.__exit__(None, None, None)
        rows = packed.reshape(self._n_shards, -1)
        bs = self._b // self._n_shards
        sub_valid = rows[:, :bs].reshape(-1).astype(bool)[: len(self._miss_idx)]
        self._cache.store_many(
            [(self._keys[i], bool(v)) for i, v in zip(self._miss_idx, sub_valid)]
        )
        valid = self._valid
        valid[self._miss_idx] = sub_valid
        # host tally (int64 — no overflow constraint on this path)
        stake = (
            np.zeros(self._n_slots, dtype=np.int64)
            if self._prior is None
            else np.asarray(self._prior, dtype=np.int64).copy()
        )
        ok = valid & (self._tx_slot >= 0) & (self._tx_slot < self._n_slots)
        np.add.at(
            stake,
            self._tx_slot[ok],
            self._powers[self._val_idx[ok]].astype(np.int64),
        )
        self._done = TallyResult(
            valid, stake, stake >= self._quorum, ~self._keep | self._pending
        )
        return self._done


def first_occurrence_mask(tx_slot, val_idx) -> np.ndarray:
    """bool[B]: True for the first occurrence of each (tx_slot, val_idx) pair.

    The reference can never count one validator's stake twice for one tx
    (first-signature-wins under a mutex, types/vote_set.go:109-131); a batch
    containing the same (tx, validator) pair twice would double-count in the
    segment-sum tally. Both verifier implementations therefore process only
    the first occurrence, in batch (arrival) order; callers re-offer dropped
    votes in a later batch if the validator still hasn't been tallied.
    """
    slot = np.asarray(tx_slot, dtype=np.int64)
    val = np.asarray(val_idx, dtype=np.int64)
    n = len(slot)
    if n == 0:
        return np.zeros(0, dtype=bool)
    # 1-D combined key: shift both axes non-negative, multiply past the
    # validator range — distinct pairs <-> distinct keys
    vmin, vmax = int(val.min()), int(val.max())
    smin = int(slot.min())
    m = vmax - vmin + 2
    combined = (slot - smin) * m + (val - vmin)
    nb = int(combined.max()) + 1
    mask = np.zeros(n, dtype=bool)
    if nb <= 4 * n + 1024:
        # dense key space (the engine's case: compact slots × small val
        # range): scatter-min of positions — ~5x faster than the sort
        # paths (r5 microbench: 38 µs vs 215 µs np.unique at B=3072)
        firstpos = np.full(nb, n, dtype=np.int64)
        np.minimum.at(firstpos, combined, np.arange(n))
        mask[firstpos[firstpos < n]] = True
    else:
        # sparse keys: stable sort + neighbor-compare (np.unique minus its
        # second key sort)
        order = np.argsort(combined, kind="stable")
        sc = combined[order]
        firsts = np.empty(n, dtype=bool)
        firsts[0] = True
        np.not_equal(sc[1:], sc[:-1], out=firsts[1:])
        mask[order[firsts]] = True
    return mask


class ScalarVoteVerifier:
    """Golden model: per-vote host verify + int64 tally (reference semantics).

    shared_cache: optional VerifyCache for co-located engines (see
    VerifyCache) — pure memoization; decisions are unchanged."""

    def __init__(self, val_set: ValidatorSet, shared_cache=None):
        self.val_set = val_set
        self._pub_keys = [v.pub_key for v in val_set]
        self._powers = val_set.powers_array()
        # one-tuple epoch stage: verify paths read it ONCE per call so a
        # concurrent restage() can never mix one epoch's keys with
        # another's powers (tuple assignment is atomic)
        self._stage = (val_set, self._pub_keys, self._powers)
        if shared_cache is True:
            shared_cache = VerifyCache()
        self.cache: VerifyCache | None = shared_cache or None

    def restage(self, new_val_set: ValidatorSet) -> bool:
        """Swap in a new validator set (epoch rotation) in place: no new
        object, no cache loss. Callers mid-``verify_and_tally`` finish
        against the stage they grabbed; the next call sees the new set."""
        pub_keys = [v.pub_key for v in new_val_set]
        powers = new_val_set.powers_array()
        self.val_set = new_val_set
        self._pub_keys = pub_keys
        self._powers = powers
        self._stage = (new_val_set, pub_keys, powers)
        return True

    def verify_and_tally(
        self,
        msgs: list[bytes],
        sigs: list[bytes],
        val_idx: np.ndarray,
        tx_slot: np.ndarray,
        n_slots: int,
        prior_stake: np.ndarray | None = None,
        quorum: int | None = None,
    ) -> TallyResult:
        n = len(msgs)
        val_set, pub_keys, powers = self._stage
        keep = first_occurrence_mask(tx_slot, val_idx)
        valid = np.zeros(n, dtype=bool)
        pending = np.zeros(n, dtype=bool)
        if self.cache is not None:
            keys = [
                VerifyCache.key(msgs[i], sigs[i], pub_keys[int(val_idx[i])])
                if keep[i] and 0 <= val_idx[i] < len(pub_keys)
                else None
                for i in range(n)
            ]
            # claim semantics (VerifyCache.lookup_or_claim_many): misses
            # another engine has in flight come back pending and are
            # DEFERRED (dropped mask), not re-verified — each unique vote
            # costs one host verify process-wide instead of one per engine
            cached, pending = self.cache.lookup_or_claim_many(keys)
            claimed = [
                keys[i]
                for i in range(n)
                if keys[i] is not None and not pending[i] and cached[i] is None
            ]
            stores = []
            try:
                # keepalive: a big miss sweep at ~50 us/verify can outlive
                # the claim TTL; stale claims would hand the same votes to
                # every other engine mid-sweep
                with self.cache.claim_keepalive(claimed):
                    for i in range(n):
                        if keys[i] is None or pending[i]:
                            continue
                        if cached[i] is not None:
                            valid[i] = cached[i]
                        else:
                            valid[i] = host_ed.verify(
                                pub_keys[int(val_idx[i])], msgs[i], sigs[i]
                            )
                            stores.append((keys[i], bool(valid[i])))
            except BaseException:
                # free every claimed-but-unverified key (waiters would
                # otherwise stall until the TTL), then surface the error
                done = {k for k, _ in stores}
                self.cache.release_many(
                    [
                        keys[i]
                        for i in range(n)
                        if keys[i] is not None
                        and not pending[i]
                        and cached[i] is None
                        and keys[i] not in done
                    ]
                )
                self.cache.store_many(stores)
                raise
            if stores:
                self.cache.store_many(stores)
        else:
            for i in range(n):
                vi = int(val_idx[i])
                if keep[i] and 0 <= vi < len(pub_keys):
                    valid[i] = host_ed.verify(pub_keys[vi], msgs[i], sigs[i])
        stake = (
            np.zeros(n_slots, dtype=np.int64)
            if prior_stake is None
            else np.asarray(prior_stake, dtype=np.int64).copy()
        )
        for i in range(n):
            s = int(tx_slot[i])
            if valid[i] and 0 <= s < n_slots:
                stake[s] += int(powers[val_idx[i]])
        q = val_set.quorum_power() if quorum is None else quorum
        return TallyResult(valid, stake, stake >= q, ~keep | pending)

    def submit(
        self,
        msgs,
        sigs,
        val_idx,
        tx_slot,
        n_slots,
        prior_stake=None,
        quorum=None,
    ) -> VerifyTicket:
        """Submit/collect surface on the eager host path: the work runs
        inline (there is no device to overlap with) and the ticket is
        already complete. Subclass overrides of verify_and_tally are
        honored — submit always routes through the instance's own
        verify_and_tally."""
        return ReadyTicket(
            self.verify_and_tally(
                msgs, sigs, val_idx, tx_slot, n_slots,
                prior_stake=prior_stake, quorum=quorum,
            )
        )


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


class _ShapeSet(set):
    """Lock-guarded ``shapes_used``: the engine thread adds shapes from
    the dispatch paths while the BackgroundWarmer thread probes
    membership, discards failed warm dispatches, and snapshots the set —
    a plain set here is a real data race (the old ``_copy_shape_set``
    RuntimeError retry loop papered over concurrent-resize crashes, and
    the race auditor flags the unlocked add/discard pair). Subclassing
    ``set`` keeps reader idiom (``set(dv.shapes_used)``, ``in``) intact;
    mutators and membership go through a leaf lock, and readers that want
    a consistent copy call ``snapshot()``."""

    def __init__(self, name: str):
        super().__init__()
        self._mtx = make_lock(name + "._mtx")
        self._sh = shared_field(name)  # txlint: shared(self._mtx)
        # dispatches per shape (add() is called once per dispatch): what
        # chip_smoke.py prints to show which rungs the traffic rode
        self._counts: dict[tuple, int] = {}

    def add(self, shape) -> None:
        with self._mtx:
            self._sh.note_write()
            set.add(self, shape)
            self._counts[shape] = self._counts.get(shape, 0) + 1

    def counts(self) -> dict[tuple, int]:
        with self._mtx:
            self._sh.note_read()
            return dict(self._counts)

    def discard(self, shape) -> None:
        with self._mtx:
            self._sh.note_write()
            set.discard(self, shape)

    def __contains__(self, shape) -> bool:
        with self._mtx:
            self._sh.note_read()
            return set.__contains__(self, shape)

    def snapshot(self) -> set:
        with self._mtx:
            self._sh.note_read()
            return set(self)


class _DeviceStage:
    """One epoch's device constants, bundled so the submit paths read a
    SINGLE attribute and can never mix one epoch's pubkey tables with
    another's powers mid-rotation (``self._stage = ...`` is atomic; a
    batch in flight finishes against the stage it grabbed).

    ``pub_keys``/``val_set`` are the REAL (unpadded) set; ``powers`` /
    ``tables_dev`` / ``powers_dev`` are padded to the verifier's
    validator capacity so every epoch of a run shares the exact compiled
    shapes (restage = two device_puts, zero compiles)."""

    __slots__ = (
        "val_set", "pub_keys", "epoch", "powers", "tables_dev", "powers_dev"
    )

    def __init__(self, val_set, pub_keys, epoch, powers, tables_dev, powers_dev):
        self.val_set = val_set
        self.pub_keys = pub_keys
        self.epoch = epoch
        self.powers = powers
        self.tables_dev = tables_dev
        self.powers_dev = powers_dev


class DeviceVoteVerifier:
    """Batched device verify + tally behind the same interface.

    Per-validator-set-epoch constants (decompressed pubkey window tables,
    voting powers) live on the host as numpy and are gathered per batch;
    the curve math and the segment-sum tally run on device. With a mesh,
    the vote axis is sharded and partial stake tallies are psum-combined
    (parallel.mesh.sharded_verify_and_tally).

    Validator-set churn: the per-epoch constants are padded to
    ``capacity`` (next power of two >= the genesis set size) and bundled
    in one ``_DeviceStage``; ``restage()`` swaps the bundle in place so
    an epoch rotation costs two host->device transfers and NO recompile —
    the bucket ladder is keyed by batch size, never by set identity.
    """

    def __init__(
        self,
        val_set: ValidatorSet,
        mesh=None,
        buckets=DEFAULT_BUCKETS,
        shared_cache: "VerifyCache | bool | None" = None,
        host_prep_workers: int = 0,
        host_prep_backend: str = "thread",
        staging_ring: int = 2,
    ):
        # cross-engine verify-result sharing (VerifyCache docstring):
        # True = own cache; an instance = share with other verifiers
        if shared_cache is True:
            self.cache: VerifyCache | None = VerifyCache()
        else:
            self.cache = shared_cache or None
        self.buckets = buckets
        # the engine must not drain batches beyond the largest bucket:
        # past it, bucket_size degrades to exact-size rounding and every
        # new batch size triggers a fresh (minutes-long on TPU) compile
        self.max_batch = max(buckets)
        # cached-path miss sets get a finer ladder (claims shrink them to
        # ~1/N_engines of a drain, i.e. quarter-drains for the 4-engine
        # LocalNet; light-load steps are far smaller still — a handful of
        # misses padded to a wide program cost the full device step,
        # dominating p50 at 10% offered load, r4 verdict item). Note the
        # actual effect depends on the bucket spacing: for the bench's
        # (bucket, 4*bucket) pair this adds bucket/4 and bucket/16 (e.g.
        # 1024 and 256 at bucket 4096); for the 4x-spaced DEFAULT_BUCKETS
        # it adds nothing (quarters coincide with existing buckets). Every
        # extra shape is a one-time compile banked in the persistent
        # cache — the ladder deliberately stops at /16 rather than going
        # to the 64 floor, trading the last slice of light-load p50
        # against about a minute of first-compile per extra shape.
        self.miss_buckets = tuple(
            sorted(
                {max(64, b // 16) for b in buckets}
                | {max(64, b // 4) for b in buckets}
                | set(buckets)
            )
        )
        self.mesh = mesh
        # every (kind, batch-bucket, slot-bucket) shape this verifier has
        # dispatched — the shape-warm registry (engine.shapes) snapshots it
        # after prewarm and diffs it after a run to detect in-run compiles
        self.shapes_used: set[tuple] = _ShapeSet("verifier.DeviceVoteVerifier.shapes_used")
        # kick the native prep build NOW (cc -O3, seconds when stale): the
        # first lazy build would otherwise land inside the first verify
        # step, stalling the engine right as the node comes under load
        from . import native as _native

        _native.available()

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from .parallel.mesh import (
                VOTE_AXIS,
                sharded_compact_step_packed_cached,
            )

            self._n_shards = mesh.size
            self._fn = sharded_compact_step_packed_cached(mesh)
            # per-batch staging shardings: padded vote-axis arrays are
            # device_put split across the mesh, the prior-stake vector
            # replicated — explicit placement so dispatch never falls
            # back to an implicit host->device-0 transfer + reshard, and
            # the compiled programs see one canonical input layout per
            # bucket (zero-recompile across epoch restages, same as the
            # single-device ladder)
            self._vote_sharding = NamedSharding(self.mesh, PartitionSpec(VOTE_AXIS))
            self._rep_sharding = NamedSharding(self.mesh, PartitionSpec())
        else:
            self._n_shards = 1
            self._fn = tally.compact_step_packed_jit()
            self._vote_sharding = None
            self._rep_sharding = None
        # sharded host-prep pool (engine.hostprep): sized by the FIRST
        # sizer — co-located engines sharing this verifier share one pool
        # (ensure_host_pool), so worker count doesn't multiply per node
        self._host_pool = None
        self.host_prep_workers = 0
        self.host_prep_backend = "thread"
        self._stats_mtx = make_lock("verifier.DeviceVoteVerifier._stats_mtx")
        # host-prep stage seconds (prep_stats()): wall time inside
        # prepare_compact on the dispatch paths, and the slice of it spent
        # waiting on pool shards this thread didn't run itself
        self._compact_s = 0.0
        self._compact_pool_wait_s = 0.0
        # double-buffered readback (parallel.staging.StagingRing): packed
        # device results enter the ring at dispatch and a side thread
        # pulls them to host eagerly, so batch N's device_put + dispatch
        # overlaps batch N-1's readback. <=1 = the historical synchronous
        # np.asarray at ticket.result(). Lazily built on first dispatch
        # so verifiers constructed for restage tests cost nothing.
        self.staging_depth = max(0, int(staging_ring))
        self._staging = None
        if host_prep_workers:
            self.ensure_host_pool(host_prep_workers, host_prep_backend)
        # validator capacity: the power-of-two sizes the existing 4/16/64
        # test and bench configs already compile for are their own pow2,
        # so padding is free there and gives odd-sized sets in-place
        # rotation headroom for joins
        self.capacity = _next_pow2(max(val_set.size(), 4))
        self._stage = self._build_stage(val_set)

    # -- per-epoch constants (read the stage ONCE per call; see
    #    _DeviceStage docstring) --

    @property
    def val_set(self) -> ValidatorSet:
        return self._stage.val_set

    @property
    def _pub_keys(self) -> list:
        return self._stage.pub_keys

    @property
    def epoch(self):
        return self._stage.epoch

    @property
    def _powers(self) -> np.ndarray:
        return self._stage.powers

    @property
    def _tables_dev(self):
        return self._stage.tables_dev

    @property
    def _powers_dev(self):
        return self._stage.powers_dev

    def ensure_host_pool(self, workers: int, backend: str = "thread"):
        """Attach (or return) the shared host-prep pool, idempotently.

        First caller with workers > 1 sizes it — backend included; later
        callers — the other engines sharing this verifier — reuse it
        regardless of the count or backend they ask for, so a 4-node
        LocalNet over one shared verifier runs ONE pool, not four.
        ``backend="process"`` degrades to threads if workers can't spawn
        (engine.hostprep.make_host_pool). Returns the pool (None when
        serial)."""
        if workers and workers > 1 and self._host_pool is None:
            with self._stats_mtx:
                if self._host_pool is None:
                    from .engine.hostprep import make_host_pool

                    pool = make_host_pool(
                        workers, backend=backend, name="hostprep-verify"
                    )
                    self.host_prep_workers = pool.workers
                    self.host_prep_backend = pool.backend
                    self._host_pool = pool
        return self._host_pool

    def _prepare(self, msgs, sigs, val_idx, epoch) -> "ed25519_batch.CompactBatch":
        """prepare_compact through the host pool (when attached), with
        stage-seconds accounting for prep_stats()."""
        t0 = monotonic()
        batch = ed25519_batch.prepare_compact(
            msgs, sigs, val_idx, epoch, pool=self._host_pool
        )
        dt = monotonic() - t0
        with self._stats_mtx:
            self._compact_s += dt
            self._compact_pool_wait_s += batch.pool_wait_s
        return batch

    def _stage_readback(self, packed):
        """Enter a just-dispatched device array into the staging ring.

        Lazily builds the ring on first dispatch (under ``_stats_mtx`` —
        one ring per verifier, shared by every engine). Returns the
        handle ``_force_readback`` understands: a ``_RingHandle`` when
        staged, the raw device array when the ring is disabled
        (``staging_ring <= 1``)."""
        if self.staging_depth < 2:
            return packed
        ring = self._staging
        if ring is None:
            with self._stats_mtx:
                ring = self._staging
                if ring is None:
                    from .parallel.staging import StagingRing

                    ring = StagingRing(self.staging_depth, name="verify-staging")
                    self._staging = ring
        return _RingHandle(ring, ring.submit(packed))

    def staging_stats(self) -> dict | None:
        """Staging-ring counters (None until the first staged dispatch)."""
        ring = self._staging
        return None if ring is None else ring.stats()

    def prep_stats(self) -> dict:
        """Host-prep stage seconds across every engine sharing this
        verifier (bench result JSON + profile_host.py host-pool lines)."""
        with self._stats_mtx:
            out = {
                "compact_s": self._compact_s,
                "compact_pool_wait_s": self._compact_pool_wait_s,
                "host_prep_workers": self.host_prep_workers,
                "host_prep_backend": self.host_prep_backend,
            }
        if self._host_pool is not None:
            out["pool"] = self._host_pool.stats()
        return out

    def _build_stage(self, val_set: ValidatorSet) -> _DeviceStage:
        # int32 device tally: with dedup, per-slot batch stake and prior
        # stake are each <= total power, so their sum stays < 2^31 only if
        # total power < 2^30. Larger sets take the scalar (int64) path.
        if val_set.total_voting_power() >= 2**30:
            raise ValueError(
                "total voting power >= 2^30: use ScalarVoteVerifier "
                "(device tally is int32)"
            )
        pub_keys = [v.pub_key for v in val_set]
        pad = self.capacity - len(pub_keys)
        if pad < 0:
            raise ValueError(
                f"validator set of {len(pub_keys)} exceeds staged "
                f"capacity {self.capacity}"
            )
        # pad table rows carry power 0 and an all-zero pubkey (no known
        # private key), and the engine's address->index map never yields a
        # pad index — a vote can neither verify against nor draw stake
        # from the pad range
        epoch = ed25519_batch.EpochTables(pub_keys + [b"\x00" * 32] * pad)
        powers = np.zeros(self.capacity, np.int32)
        powers[: len(pub_keys)] = val_set.powers_array().astype(np.int32)
        import jax

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # pre-replicate the per-epoch device constants across the mesh
            rep = NamedSharding(self.mesh, PartitionSpec())
            tables_dev = jax.device_put(epoch.tables, rep)
            powers_dev = jax.device_put(powers, rep)
        else:
            tables_dev = epoch.device_tables()
            powers_dev = jax.numpy.asarray(powers)
        return _DeviceStage(val_set, pub_keys, epoch, powers, tables_dev, powers_dev)

    def restage(self, new_val_set: ValidatorSet) -> bool:
        """Swap the per-epoch device constants for a NEW validator set
        without recompiling: same padded shapes, same bucket ladder, same
        VerifyCache, same compiled programs. Returns False when the new
        set exceeds ``capacity`` — the caller must fall back to building
        a fresh verifier. Raises ValueError on the int32 tally cap, like
        construction would. Idempotent for an unchanged set; concurrent
        submitters finish against whichever stage they grabbed."""
        if new_val_set.size() > self.capacity:
            return False
        old = self._stage
        if new_val_set.hash() == old.val_set.hash():
            return True
        stage = self._build_stage(new_val_set)
        # the compile contract this subsystem exists to keep: shapes are
        # a function of capacity + bucket ladder, never of set identity
        assert stage.tables_dev.shape == old.tables_dev.shape, (
            "restage changed the staged table shape"
        )
        assert stage.powers_dev.shape == old.powers_dev.shape, (
            "restage changed the staged powers shape"
        )
        self._stage = stage
        return True

    def warmup(self, n: int = 1, full: bool = False) -> None:
        """Compile the kernel for the bucket shapes of an n-vote batch.

        Call ONCE before concurrent engines share this verifier: N threads
        racing to compile the same uncached shape is N redundant compiles
        of about a minute each.

        full=True additionally compiles the shapes loaded runs hit: with
        a shared cache attached, the whole _verify_only miss ladder (the
        fused shapes are unreachable while the cache is on); without one,
        the fused (batch-bucket, slot-bucket) combos — (b, b) and
        (b, smallest) for every bucket b, the combos engine drains
        produce (slots = unique txs <= votes, so slot buckets other than
        the batch's own and the floor are rare). A shape left cold here
        compiles MID-RUN on the first batch that hits it, stalling the
        pipeline for the entire compile (r5 measured: a 169 s throughput
        phase containing ~160 s of one such compile)."""
        self.verify_and_tally(
            [b""] * n, [b""] * n, np.zeros(n, np.int64), np.zeros(n, np.int64), 1
        )
        if self.cache is not None:
            # cached path: every device call is _verify_only over a miss
            # set. Default warmup(n) keeps its documented contract — every
            # shape an n-vote batch can hit must be warm, which on the
            # finer miss ladder means every miss bucket up to n's coarse
            # bucket (a smaller miss set pads to a smaller program).
            # full=True warms the whole ladder.
            limit = self.max_batch if full else bucket_size(n, self.buckets)
            for b in self.miss_buckets:
                if b > limit:
                    break
                self._verify_only(
                    [b"warm-%d" % i for i in range(b)],
                    [b"\x00" * 64] * b,
                    np.zeros(b, np.int64),
                )
            return
        if not full:
            return
        smallest = self.buckets[0]
        for b in self.buckets:
            combos = [(b, b)] if b == smallest else [(b, b), (b, smallest)]
            for nn, n_slots in combos:
                self.verify_and_tally(
                    [b""] * nn, [b""] * nn,
                    np.zeros(nn, np.int64), np.zeros(nn, np.int64),
                    n_slots,
                )

    def verify_and_tally(
        self,
        msgs: list[bytes],
        sigs: list[bytes],
        val_idx: np.ndarray,
        tx_slot: np.ndarray,
        n_slots: int,
        prior_stake: np.ndarray | None = None,
        quorum: int | None = None,
    ) -> TallyResult:
        # the blocking call IS submit + collect: one code path, so the
        # pipelined engine and the serial one take bit-identical decisions
        return self.submit(
            msgs, sigs, val_idx, tx_slot, n_slots,
            prior_stake=prior_stake, quorum=quorum,
        ).result()

    def submit(
        self,
        msgs: list[bytes],
        sigs: list[bytes],
        val_idx: np.ndarray,
        tx_slot: np.ndarray,
        n_slots: int,
        prior_stake: np.ndarray | None = None,
        quorum: int | None = None,
    ) -> VerifyTicket:
        """Dispatch the verify+tally kernel WITHOUT forcing the readback.

        JAX dispatch is async: ``self._fn(...)`` returns as soon as the
        program is enqueued, and only ``np.asarray`` blocks on the device.
        The returned ticket defers that readback to ``result()``, so the
        caller can prep the next batch (or route the previous one) while
        the device computes this one. On the cached path the caller's
        claims are held (with keepalive) by the ticket and settled at
        ``result()``; a dispatch failure here releases them before
        raising."""
        n = len(msgs)
        val_idx = np.asarray(val_idx, dtype=np.int64)
        tx_slot = np.asarray(tx_slot, dtype=np.int32)
        keep = first_occurrence_mask(tx_slot, val_idx)
        st = self._stage  # one read: epoch-consistent tables/powers/quorum
        if self.cache is not None:
            return self._submit_cached(
                msgs, sigs, val_idx, tx_slot, n_slots, prior_stake, quorum,
                keep, st,
            )
        b = bucket_size(n, self.buckets, multiple=self._n_shards)
        # n_slots is a compiled shape too (prior_stake) — bucket it as well,
        # or every step with a new in-flight tx count would recompile the
        # whole kernel; padding slots receive no votes and slice away
        b_slots = bucket_size(n_slots, self.buckets)

        batch = self._prepare(msgs, sigs, val_idx, st.epoch)
        batch.pre_ok &= keep
        # pad to bucket: pre_ok False + slot -1 => contributes nothing
        pad = b - n
        s_nib = _pad(batch.s_nibbles, pad)
        h_nib = _pad(batch.h_nibbles, pad)
        vidx = _pad(batch.val_idx, pad)
        r_y = _pad(batch.r_y, pad)
        r_sign = _pad(batch.r_sign, pad)
        pre_ok = _pad(batch.pre_ok, pad)
        slot = np.full(b, -1, np.int32)
        slot[:n] = tx_slot

        prior = np.zeros(b_slots, np.int32)
        if prior_stake is not None:
            prior[:n_slots] = np.asarray(prior_stake, dtype=np.int32)
        q = np.int32(st.val_set.quorum_power() if quorum is None else quorum)

        self.shapes_used.add(("fused", b, b_slots))
        if self.mesh is not None:
            # explicit placement: vote-axis arrays split across the mesh
            # (b is a multiple of _n_shards by construction), prior
            # replicated — the numpy buffers hand off without an extra
            # host copy and the program never implicitly reshards
            import jax

            s_nib, h_nib, vidx, r_y, r_sign, pre_ok, slot = jax.device_put(
                (s_nib, h_nib, vidx, r_y, r_sign, pre_ok, slot),
                self._vote_sharding,
            )
            prior = jax.device_put(prior, self._rep_sharding)
        packed = self._fn(
            s_nib, h_nib, vidx, r_y, r_sign, pre_ok, slot,
            st.tables_dev, st.powers_dev, prior, q,
        )
        # ONE readback — deferred to ticket.result() and (with a staging
        # ring) already in flight on the ring thread; per-shard layout
        # [valid b/n | stake S | maj S] (tally.compact_step_packed);
        # stake/maj repeat the replicated global per shard — the ticket
        # takes shard 0's copy
        return _FusedDeviceTicket(
            self._stage_readback(packed), n, n_slots, self._n_shards, b,
            b_slots, keep,
        )

    def _submit_cached(
        self, msgs, sigs, val_idx, tx_slot, n_slots, prior_stake, quorum,
        keep, st: _DeviceStage,
    ) -> VerifyTicket:
        """Cache-aware path: device-verify only the cache misses THIS
        caller claims, tally on the host. Decisions are bit-identical to
        the fused kernel — the tally is the same prior + segment-sum over
        valid first-occurrence votes, and validity per vote is a pure
        function the cache merely memoizes. Misses another engine already
        has in flight are NOT verified here: they come back dropped=True
        and the engine re-offers them next step, by which time they are
        hits (claim semantics: VerifyCache.lookup_or_claim_many). With
        co-located engines the steady state is ~1/N_engines of the device
        work each, with no duplicated in-flight verifies — without claims
        the r5 TPU bench measured 580 votes/s (each engine paying a full
        padded device call for a tiny private miss set) vs 12k uncached."""
        n = len(msgs)
        # bound on the REAL set (st.powers is padded to capacity; an index
        # in the pad range must read as unknown-validator, not as a row)
        n_vals = len(st.pub_keys)
        keys: list[bytes | None] = [
            VerifyCache.key(msgs[i], sigs[i], st.pub_keys[int(val_idx[i])])
            if keep[i] and 0 <= val_idx[i] < n_vals
            else None
            for i in range(n)
        ]
        cached, pending = self.cache.lookup_or_claim_many(keys)
        valid = np.zeros(n, dtype=bool)
        miss_idx = []
        for i in range(n):
            if keys[i] is None or pending[i]:
                continue  # unknown validator / in-batch repeat / in flight
            if cached[i] is None:
                miss_idx.append(i)
            else:
                valid[i] = cached[i]
        q = st.val_set.quorum_power() if quorum is None else quorum
        if miss_idx:
            miss_keys = [keys[i] for i in miss_idx]
            # keepalive: the device call can exceed the claim TTL by
            # orders of magnitude (cold-shape compiles run minutes on
            # TPU); without it, expired claims trigger N concurrent
            # compiles of the same shape (VerifyCache.claim_keepalive).
            # Entered HERE, exited by the ticket at result(): the claims
            # stay owned for the whole dispatch->readback window, which
            # the pipelined engine stretches across its next batch prep.
            ka = self.cache.claim_keepalive(miss_keys)
            ka.__enter__()
            try:
                packed, b = self._dispatch_verify_only(
                    [msgs[i] for i in miss_idx],
                    [sigs[i] for i in miss_idx],
                    val_idx[miss_idx],
                    claim_keys=miss_keys,
                    stage=st,
                )
            except BaseException:
                # claims must not outlive a failed dispatch (waiters
                # would stall until the TTL) — hand them to the next asker
                ka.__exit__(None, None, None)
                self.cache.release_many(miss_keys)
                raise
            # pending claims ride the dropped mask (set by the ticket):
            # the engine re-offers them next step exactly like in-batch
            # (slot, validator) repeats
            return _CachedDeviceTicket(
                self.cache, packed, ka, miss_idx, miss_keys, keys,
                valid, tx_slot, n_slots, prior_stake, q, keep, pending,
                st.powers, val_idx, self._n_shards, b,
            )
        # all hits/deferrals: nothing to dispatch — host tally, done now
        stake = (
            np.zeros(n_slots, dtype=np.int64)
            if prior_stake is None
            else np.asarray(prior_stake, dtype=np.int64).copy()
        )
        ok = valid & (tx_slot >= 0) & (tx_slot < n_slots)
        np.add.at(
            stake, tx_slot[ok], st.powers[val_idx[ok]].astype(np.int64)
        )
        return ReadyTicket(
            TallyResult(valid, stake, stake >= q, ~keep | pending)
        )

    def _verify_only(self, msgs, sigs, val_idx) -> np.ndarray:
        """Device signature verification without the tally (slots parked
        at -1, minimal slot bucket): bool[n]. Blocking (warmup uses it);
        the cached submit path dispatches via _dispatch_verify_only and
        defers this readback to the ticket."""
        packed, b = self._dispatch_verify_only(msgs, sigs, val_idx)
        rows = _force_readback(packed).reshape(self._n_shards, -1)
        bs = b // self._n_shards
        return rows[:, :bs].reshape(-1).astype(bool)[: len(msgs)]

    def predicted_shapes(self, n: int, n_slots: int = 1) -> list[tuple]:
        """Every (kind, batch-bucket, slot-bucket) shape an n-vote /
        n_slots-tx batch can dispatch through this verifier — the
        cold-shape gate's input (engine.shapes.ShapeWarmRegistry
        .is_batch_warm). Cached config: the claimed miss subset has any
        size m <= n, so the whole miss ladder up to n's rung is
        reachable. Fused config: exactly one combo."""
        shards = self._n_shards
        if self.cache is not None:
            top = bucket_size(max(n, 1), self.miss_buckets, multiple=shards)
            shapes = []
            for b in self.miss_buckets:
                bb = bucket_size(b, self.miss_buckets, multiple=shards)
                if bb > top:
                    break
                shapes.append(("verify", bb, self.buckets[0]))
            return sorted(set(shapes))
        return [(
            "fused",
            bucket_size(n, self.buckets, multiple=shards),
            bucket_size(n_slots, self.buckets),
        )]

    def _dispatch_verify_only(
        self, msgs, sigs, val_idx, claim_keys=None, stage=None
    ):
        """Enqueue the verify-only program; returns (device_array, b)
        without forcing the readback.

        claim_keys: VerifyCache claims held for this miss set. The
        ``self._fn`` call below is where a cold shape TRACES AND COMPILES
        synchronously — about a minute on a TPU — so the claims are
        re-stamped from THIS thread on both sides of it, belt-and-braces
        with the caller's keepalive thread (ADVICE r5: a stale claim
        mid-compile hands the same keys to every co-located engine and
        piles N concurrent compiles onto one shape)."""
        n = len(msgs)
        st = stage if stage is not None else self._stage
        # fine-grained buckets: cached-path miss sets are far smaller than
        # engine drains (other engines own most votes via claims), and
        # padding a ~100-miss set to a 4096-wide program wastes the whole
        # device step (the r5 580-votes/s pathology's second half)
        b = bucket_size(n, self.miss_buckets, multiple=self._n_shards)
        # slot width stays on the coarse bucket ladder: the already-banked
        # compiled programs use it, and the tally half of the program is
        # insensitive to slot width next to the verify half
        b_slots = self.buckets[0]
        batch = self._prepare(msgs, sigs, val_idx, st.epoch)
        pad = b - n
        self.shapes_used.add(("verify", b, b_slots))
        if claim_keys and self.cache is not None:
            self.cache.heartbeat_many(claim_keys)
        vote_args = (
            _pad(batch.s_nibbles, pad),
            _pad(batch.h_nibbles, pad),
            _pad(batch.val_idx, pad),
            _pad(batch.r_y, pad),
            _pad(batch.r_sign, pad),
            _pad(batch.pre_ok, pad),
            np.full(b, -1, np.int32),
        )
        prior = np.zeros(b_slots, np.int32)
        if self.mesh is not None:
            import jax

            vote_args = jax.device_put(vote_args, self._vote_sharding)
            prior = jax.device_put(prior, self._rep_sharding)
        packed = self._fn(
            *vote_args,
            st.tables_dev,
            st.powers_dev,
            prior,
            np.int32(1),
        )
        if claim_keys and self.cache is not None:
            # the dispatch (and any compile inside it) is behind us: stamp
            # the claims once more so the readback window starts fresh
            self.cache.heartbeat_many(claim_keys)
        return self._stage_readback(packed), b


class ResilientVoteVerifier:
    """Graceful degradation around a device verifier.

    Policy, in order:

    1. bounded retry — a device error is retried up to ``max_attempts``
       times with exponential backoff (base*2^k, capped at backoff_max);
    2. CPU fallback — on exhaustion the verifier DEMOTES: the batch (and
       subsequent batches) are served by ``ScalarVoteVerifier``, the
       golden model, so commits keep flowing at host speed instead of the
       vote path erroring;
    3. recovery probing — while demoted, one caller per ``probe_interval``
       offers its live batch to the device again; success RE-PROMOTES,
       failure re-arms the probe timer and falls back.

    Decisions are unaffected by which path serves a batch: the scalar and
    device verifiers return bit-identical masks and quorum decisions
    (module docstring), so degradation is observable only as latency and
    in the counters here. Used as a ``VerifierMux`` inner (or directly as
    an engine verifier) this keeps a device failure from reaching
    ``_fail_queued`` — the mux's inner call succeeds on the CPU path, so
    queued requests are answered instead of errored.

    The device's shared VerifyCache (when present) is handed to the
    fallback too: verdicts cached by either path serve both, and claims
    released by a failed device call are re-claimable by the fallback.

    ``sleep``/``clock`` are injectable for deterministic tests.
    """

    def __init__(
        self,
        device,
        fallback=None,
        max_attempts: int = 3,
        backoff_base: float = 0.05,
        backoff_max: float = 2.0,
        probe_interval: float = 5.0,
        sleep=time.sleep,
        clock=time.monotonic,
    ):
        self.device = device
        self.val_set = device.val_set
        self.cache = getattr(device, "cache", None)
        if fallback is None:
            fallback = ScalarVoteVerifier(self.val_set, shared_cache=self.cache)
        self.fallback = fallback
        mb = getattr(device, "max_batch", None)
        if mb is not None:
            self.max_batch = mb
        self.max_attempts = max(1, max_attempts)
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.probe_interval = probe_interval
        self._sleep = sleep
        self._clock = clock
        self._lock = make_lock("verifier.ResilientVoteVerifier._lock")
        self._device_ok = True
        self._next_probe = 0.0
        # observability (bench/RPC surface them; tests assert transitions)
        self.device_failures = 0
        self.fallback_calls = 0
        self.demotions = 0
        self.repromotions = 0
        self.last_error: Exception | None = None
        self.on_state_change = lambda healthy: None

    @property
    def device_healthy(self) -> bool:
        return self._device_ok

    def _should_try_device(self) -> bool:
        with self._lock:
            if self._device_ok:
                return True
            now = self._clock()
            if now >= self._next_probe:
                # re-arm BEFORE probing so concurrent callers don't all
                # pay the probe latency; exactly one per interval does
                self._next_probe = now + self.probe_interval
                return True
            return False

    def _mark_device(self, healthy: bool) -> None:
        with self._lock:
            changed = self._device_ok != healthy
            self._device_ok = healthy
            if changed:
                if healthy:
                    self.repromotions += 1
                else:
                    self.demotions += 1
                    self._next_probe = self._clock() + self.probe_interval
        if changed:
            try:
                self.on_state_change(healthy)
            except Exception:
                pass

    def warmup(self, n: int = 1, full: bool = False) -> None:
        try:
            self.device.warmup(n, full=full)
        except Exception as e:
            with self._lock:
                self.device_failures += 1
                self.last_error = e
            self._mark_device(False)

    def restage(self, new_val_set) -> bool:
        """Epoch rotation passthrough: restage the device lane in place
        (keeping its compiled shapes, cache, and the degradation counters
        here) and mirror the set onto the CPU fallback so a demoted node
        rotates identically. False = device can't restage (capacity) —
        the caller rebuilds the whole resilient stack."""
        rs = getattr(self.device, "restage", None)
        if rs is None or not rs(new_val_set):
            return False
        self.val_set = new_val_set
        fb = getattr(self.fallback, "restage", None)
        if fb is not None:
            fb(new_val_set)
        return True

    def verify_and_tally(
        self,
        msgs,
        sigs,
        val_idx,
        tx_slot,
        n_slots,
        prior_stake=None,
        quorum=None,
    ) -> TallyResult:
        if self._should_try_device():
            delay = self.backoff_base
            for attempt in range(self.max_attempts):
                try:
                    result = self.device.verify_and_tally(
                        msgs, sigs, val_idx, tx_slot, n_slots,
                        prior_stake=prior_stake, quorum=quorum,
                    )
                except Exception as e:
                    with self._lock:
                        self.device_failures += 1
                        self.last_error = e
                    if attempt + 1 < self.max_attempts:
                        self._sleep(min(delay, self.backoff_max))
                        delay *= 2
                else:
                    self._mark_device(True)
                    return result
            self._mark_device(False)
        with self._lock:
            self.fallback_calls += 1
        return self.fallback.verify_and_tally(
            msgs, sigs, val_idx, tx_slot, n_slots,
            prior_stake=prior_stake, quorum=quorum,
        )

    def submit(
        self,
        msgs,
        sigs,
        val_idx,
        tx_slot,
        n_slots,
        prior_stake=None,
        quorum=None,
    ) -> VerifyTicket:
        """Async dispatch with the degradation policy at COLLECT time.

        A healthy device gets one async dispatch attempt; a dispatch
        error (or an error surfacing at the ticket's readback) is
        recorded and the batch re-runs through the full blocking
        verify_and_tally policy — bounded retry, backoff, CPU fallback,
        probe re-promotion — so a pipelined engine degrades exactly like
        a serial one, just one ticket later."""
        args = (msgs, sigs, val_idx, tx_slot, n_slots, prior_stake, quorum)
        if self._should_try_device():
            sub = getattr(self.device, "submit", None)
            if sub is not None:
                try:
                    inner = sub(
                        msgs, sigs, val_idx, tx_slot, n_slots,
                        prior_stake=prior_stake, quorum=quorum,
                    )
                except Exception as e:
                    with self._lock:
                        self.device_failures += 1
                        self.last_error = e
                    # fall through: the blocking path owns retry/fallback
                else:
                    return _ResilientTicket(self, inner, args)
        return ReadyTicket(
            self.verify_and_tally(
                msgs, sigs, val_idx, tx_slot, n_slots,
                prior_stake=prior_stake, quorum=quorum,
            )
        )


class _ResilientTicket(VerifyTicket):
    """Device ticket wrapped in the resilience policy: a readback failure
    records the device error and re-serves the batch via the outer
    verifier's blocking policy path (retry/backoff/fallback)."""

    __slots__ = ("_outer", "_inner", "_args", "_done")

    def __init__(self, outer: ResilientVoteVerifier, inner: VerifyTicket, args):
        self._outer = outer
        self._inner = inner
        self._args = args
        self._done: TallyResult | None = None

    @property
    def ready_t(self) -> float | None:
        # a batch re-served by the policy path has no stamp (the failed
        # inner ticket never set one): the collect time stands in
        return self._inner.ready_t

    def result(self) -> TallyResult:
        if self._done is not None:
            return self._done
        outer = self._outer
        try:
            res = self._inner.result()
        except Exception as e:
            with outer._lock:
                outer.device_failures += 1
                outer.last_error = e
            msgs, sigs, val_idx, tx_slot, n_slots, prior, quorum = self._args
            # cache claims were settled by the failed ticket (release on
            # readback error), so the policy re-run can re-claim them
            res = outer.verify_and_tally(
                msgs, sigs, val_idx, tx_slot, n_slots,
                prior_stake=prior, quorum=quorum,
            )
        else:
            outer._mark_device(True)
        self._done = res
        return res


def _pad(a: np.ndarray, pad: int) -> np.ndarray:
    if pad == 0:
        return a
    return np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])


class VerifierMux:
    """Merge concurrent engines' verify calls into one device invocation.

    N colocated validators (an in-process net, or one host running several
    nodes) each run an engine that calls ``verify_and_tally`` — serially
    that is N device round trips per wave, and the fixed per-call cost
    (dispatch + readback) dominates at small batches. The mux
    presents the same blocking ``verify_and_tally`` to each engine and a
    dispatcher thread concatenates concurrent requests — votes appended,
    each request's tx slots shifted into a disjoint slot range — into ONE
    inner call, then splits the results. Decisions are bit-identical to
    separate calls: per-vote verification is independent, the slot shift
    keeps each request's tally rows private, and in-batch (slot, validator)
    dedup cannot cross requests because shifted slot ids never collide.

    Constraints: every caller must share the inner verifier's validator
    set (quorum overrides are not mergeable — reject them), and a
    validator-set rotation means callers should detach to their own
    verifier (engine.update_state does).
    """

    def __init__(
        self,
        inner,
        max_batch_per_caller: int = 4096,
        gather_wait: float = 0.01,
        pipeline_depth: int = 2,
    ):
        import queue as _q
        import threading as _t

        self.inner = inner
        self.val_set = inner.val_set
        # the engine sizes drains off this; the merged batch may hold up to
        # inner.max_batch votes across callers
        self.max_batch = max_batch_per_caller
        self.gather_wait = gather_wait
        # merged device calls kept in flight when the inner verifier has a
        # submit/collect split: the dispatcher launches batch N+1 while the
        # collector still awaits batch N's readback (in submission order).
        # <=1 degrades to the serial serve loop.
        self.pipeline_depth = max(1, pipeline_depth)
        self._q: _q.SimpleQueue = _q.SimpleQueue()
        self._running = False
        self._thread: _t.Thread | None = None
        self._collector: _t.Thread | None = None
        self._lock = make_lock("verifier.VerifierMux._lock")
        # dispatcher generation: a dispatcher that outlives its stop() (a
        # long device batch ran past the join timeout) exits on its own at
        # the next loop turn instead of racing a restarted dispatcher for
        # the queue
        self._gen = 0

    def start(self) -> None:
        import queue as _q
        import threading as _t

        with self._lock:
            if self._running:
                return
            self._running = True
            self._gen += 1
            gen = self._gen
        # a FRESH in-flight queue per generation: a retired dispatcher's
        # exit sentinel must not kill a restarted generation's collector
        pending: _q.Queue = _q.Queue(maxsize=self.pipeline_depth)
        self._collector = _t.Thread(
            target=self._collect_run, args=(pending,),
            name="verifier-mux-collect", daemon=True,
        )
        self._collector.start()
        self._thread = _t.Thread(
            target=self._run, args=(gen, pending), name="verifier-mux",
            daemon=True,
        )
        self._thread.start()

    def stop(self) -> None:
        with self._lock:
            self._running = False
        self._q.put(None)
        thread = self._thread
        collector = self._collector
        self._thread = None
        self._collector = None
        if thread is not None:
            thread.join(timeout=5)
            if thread.is_alive():
                # dispatcher is mid-batch: the queue is still its to drain
                # (it fails leftovers itself on exit — see _run); draining
                # here would steal the sentinel it needs
                return
        if collector is not None:
            # the dispatcher's exit pushed the collector's sentinel; give
            # in-flight device readbacks time to drain in order
            collector.join(timeout=10)
        # requests still queued (behind the sentinel, or enqueued by a
        # caller that raced the _running check) would otherwise strand
        # their threads in done.wait() forever (r3 advisor low): fail them
        self._fail_queued(RuntimeError("VerifierMux stopped"))

    def _fail_queued(self, err: Exception) -> None:
        import queue as _q

        while True:
            try:
                req = self._q.get_nowait()
            except _q.Empty:
                return
            if req is None:
                continue
            with self._lock:
                if req.claimed:
                    continue
                req.claimed = True
            req.error = err
            req.done.set()

    def warmup(self, n: int = 1, full: bool = False) -> None:
        self.inner.warmup(n, full=full)

    def _make_req(self, msgs, sigs, val_idx, tx_slot, n_slots, prior_stake):
        import threading as _t

        return _MuxReq(
            msgs, sigs,
            np.asarray(val_idx, np.int64),
            np.asarray(tx_slot, np.int64),
            n_slots,
            None if prior_stake is None else np.asarray(prior_stake, np.int64),
            _t.Event(),
        )

    def verify_and_tally(
        self, msgs, sigs, val_idx, tx_slot, n_slots,
        prior_stake=None, quorum=None,
    ) -> TallyResult:
        if quorum is not None and quorum != self.val_set.quorum_power():
            raise ValueError("VerifierMux cannot merge per-call quorum overrides")
        if not self._running:  # not started: passthrough (tests, solo use)
            return self.inner.verify_and_tally(
                msgs, sigs, val_idx, tx_slot, n_slots, prior_stake=prior_stake
            )
        req = self._make_req(msgs, sigs, val_idx, tx_slot, n_slots, prior_stake)
        self._q.put(req)
        return self._await(req)

    def submit(
        self, msgs, sigs, val_idx, tx_slot, n_slots,
        prior_stake=None, quorum=None,
    ) -> VerifyTicket:
        """Enqueue for merging and return immediately: the caller's engine
        preps its next batch while the dispatcher gathers, merges, and
        (asynchronously) runs this one. ticket.result() == the blocking
        verify_and_tally, including the reclaim-on-stop path."""
        if quorum is not None and quorum != self.val_set.quorum_power():
            raise ValueError("VerifierMux cannot merge per-call quorum overrides")
        if not self._running:  # not started: passthrough (tests, solo use)
            sub = getattr(self.inner, "submit", None)
            if sub is not None:
                return sub(
                    msgs, sigs, val_idx, tx_slot, n_slots,
                    prior_stake=prior_stake,
                )
            return ReadyTicket(
                self.inner.verify_and_tally(
                    msgs, sigs, val_idx, tx_slot, n_slots,
                    prior_stake=prior_stake,
                )
            )
        req = self._make_req(msgs, sigs, val_idx, tx_slot, n_slots, prior_stake)
        self._q.put(req)
        return _MuxTicket(self, req)

    def _await(self, req) -> TallyResult:
        # bounded wait + liveness re-check: if the mux stopped after the
        # _running check at enqueue, the dispatcher may never see this
        # request — claim it back and serve it inline on the inner verifier
        while not req.done.wait(timeout=1.0):
            if not self._running:
                with self._lock:
                    orphaned = not req.claimed
                    if orphaned:
                        req.claimed = True
                if orphaned:
                    return self.inner.verify_and_tally(
                        req.msgs, req.sigs, req.val_idx, req.tx_slot,
                        req.n_slots, prior_stake=req.prior,
                    )
                req.done.wait()  # claimed by the dispatcher: finish soon
                break
        if req.error is not None:
            raise req.error
        return req.result

    def _run(self, gen: int, pending) -> None:
        import queue as _q
        import time as _time

        def retired() -> bool:
            # stopped, or superseded by a restart while we ran a long batch
            return not self._running or self._gen != gen

        inner_cap = getattr(self.inner, "max_batch", 1 << 30)
        try:
            while True:
                if retired():
                    # we own the queue until we exit: fail anything left so
                    # no caller strands (stop() skips its drain while we live)
                    if self._gen == gen:
                        self._fail_queued(RuntimeError("VerifierMux stopped"))
                    return
                req = self._q.get()
                if req is None:
                    if retired():
                        if self._gen == gen:
                            self._fail_queued(RuntimeError("VerifierMux stopped"))
                        return
                    continue
                batch = [req]
                total = len(req.msgs)
                deadline = _time.monotonic() + self.gather_wait
                while total < inner_cap:
                    remaining = deadline - _time.monotonic()
                    try:
                        nxt = self._q.get(timeout=max(remaining, 0)) if remaining > 0 else self._q.get_nowait()
                    except _q.Empty:
                        break
                    if nxt is None:
                        if not self._running:
                            self._serve(batch)
                            if self._gen == gen:
                                self._fail_queued(RuntimeError("VerifierMux stopped"))
                            return
                        continue
                    if total + len(nxt.msgs) > inner_cap:
                        self._q.put(nxt)  # next round (order among waiters is free)
                        break
                    batch.append(nxt)
                    total += len(nxt.msgs)
                self._dispatch(batch, pending)
        finally:
            # ALL dispatcher exits release the collector (in-flight tickets
            # drain in submission order first — Queue is FIFO)
            pending.put(None)

    def _claim(self, batch: list) -> list:
        # claim every request first: one already claimed was failed by
        # stop() or reclaimed by its caller — it is no longer ours to serve
        with self._lock:
            batch = [r for r in batch if not r.claimed]
            for r in batch:
                r.claimed = True
        return batch

    @staticmethod
    def _merge(batch: list):
        """Concatenate claimed requests into one call's arguments, each
        request's tx slots shifted into a disjoint slot range."""
        msgs, sigs, vidx, slots, priors = [], [], [], [], []
        off = 0
        for r in batch:
            msgs.extend(r.msgs)
            sigs.extend(r.sigs)
            vidx.append(r.val_idx)
            slots.append(r.tx_slot + off)
            priors.append(
                np.zeros(r.n_slots, np.int64) if r.prior is None else r.prior
            )
            off += r.n_slots
        return (
            msgs, sigs, np.concatenate(vidx), np.concatenate(slots), off,
            np.concatenate(priors),
        )

    @staticmethod
    def _split(batch: list, merged: TallyResult) -> None:
        """Hand each request its slice of the merged result."""
        if len(batch) == 1:
            batch[0].result = merged
            return
        v_off = s_off = 0
        for r in batch:
            nv, ns = len(r.msgs), r.n_slots
            r.result = TallyResult(
                merged.valid[v_off : v_off + nv],
                merged.stake[s_off : s_off + ns],
                merged.maj23[s_off : s_off + ns],
                merged.dropped[v_off : v_off + nv],
            )
            v_off += nv
            s_off += ns

    def _dispatch(self, batch: list, pending) -> None:
        """Claim + merge + async-submit one gathered batch; completion is
        the collector's job. Falls back to synchronous serving when the
        inner verifier has no submit split."""
        sub = getattr(self.inner, "submit", None)
        if sub is None or self.pipeline_depth <= 1:
            self._serve(batch)
            return
        batch = self._claim(batch)
        if not batch:
            return
        try:
            if len(batch) == 1:
                r = batch[0]
                ticket = sub(
                    r.msgs, r.sigs, r.val_idx, r.tx_slot, r.n_slots,
                    prior_stake=r.prior,
                )
            else:
                msgs, sigs, vidx, slots, off, priors = self._merge(batch)
                ticket = sub(
                    msgs, sigs, vidx, slots, off, prior_stake=priors
                )
        except Exception as e:  # dispatch failed: deliver to every waiter
            for r in batch:
                r.error = e
                r.done.set()
            return
        # blocks while pipeline_depth batches are already in flight —
        # backpressure instead of unbounded dispatch queueing
        pending.put((batch, ticket))

    def _collect_run(self, pending) -> None:
        """Resolve in-flight tickets in submission order (FIFO queue) and
        deliver each request its slice."""
        while True:
            item = pending.get()
            if item is None:
                return
            batch, ticket = item
            try:
                merged = ticket.result()
            except Exception as e:  # deliver the failure to every waiter
                for r in batch:
                    r.error = e
                    r.done.set()
                continue
            self._split(batch, merged)
            for r in batch:
                r.ready_t = ticket.ready_t  # the merged batch's, for each waiter
                r.done.set()

    def _serve(self, batch: list) -> None:
        batch = self._claim(batch)
        if not batch:
            return
        try:
            if len(batch) == 1:
                r = batch[0]
                r.result = self.inner.verify_and_tally(
                    r.msgs, r.sigs, r.val_idx, r.tx_slot, r.n_slots,
                    prior_stake=r.prior,
                )
            else:
                msgs, sigs, vidx, slots, off, priors = self._merge(batch)
                merged = self.inner.verify_and_tally(
                    msgs, sigs, vidx, slots, off, prior_stake=priors
                )
                self._split(batch, merged)
        except Exception as e:  # deliver the failure to every waiter
            for r in batch:
                r.error = e
        finally:
            for r in batch:
                r.done.set()


class _MuxTicket(VerifyTicket):
    """Caller-side handle to an enqueued mux request. result() runs the
    same await/reclaim protocol as the blocking verify_and_tally."""

    __slots__ = ("_mux", "_req", "_done")

    def __init__(self, mux: VerifierMux, req):
        self._mux = mux
        self._req = req
        self._done: TallyResult | None = None

    def result(self) -> TallyResult:
        if self._done is None:
            self._done = self._mux._await(self._req)
        return self._done

    @property
    def ready_t(self) -> float | None:
        return self._req.ready_t


class _MuxReq:
    __slots__ = (
        "msgs", "sigs", "val_idx", "tx_slot", "n_slots", "prior",
        "done", "result", "error", "claimed", "ready_t",
    )

    def __init__(self, msgs, sigs, val_idx, tx_slot, n_slots, prior, done):
        self.msgs = msgs
        self.sigs = sigs
        self.val_idx = val_idx
        self.tx_slot = tx_slot
        self.n_slots = n_slots
        self.prior = prior
        self.done = done
        self.result = None
        self.error = None
        self.ready_t: float | None = None  # the merged ticket's stamp
        # exactly-once service marker (set under the mux lock): the
        # dispatcher claims requests it serves; a caller that raced stop()
        # claims its own request back and serves it inline — never both
        self.claimed = False
