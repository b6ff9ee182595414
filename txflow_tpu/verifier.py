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

import time
from dataclasses import dataclass

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
    it may be called exactly once per ticket from any thread.

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
    """Dispatched fused kernel: readback + unpack at result()."""

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
    """Golden model: per-vote host verify + int64 tally (reference semantics)."""

    def __init__(self, val_set: ValidatorSet):
        self.val_set = val_set
        self._pub_keys = [v.pub_key for v in val_set]
        self._powers = val_set.powers_array()
        # one-tuple epoch stage: verify paths read it ONCE per call so a
        # concurrent restage() can never mix one epoch's keys with
        # another's powers (tuple assignment is atomic)
        self._stage = (val_set, self._pub_keys, self._powers)

    def restage(self, new_val_set: ValidatorSet) -> bool:
        """Swap in a new validator set (epoch rotation) in place: no new
        object. Callers mid-``verify_and_tally`` finish against the stage
        they grabbed; the next call sees the new set."""
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
        return TallyResult(valid, stake, stake >= q, ~keep)

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
        host_prep_workers: int = 0,
        host_prep_backend: str = "thread",
        staging_ring: int = 2,
    ):
        self.buckets = buckets
        # the engine must not drain batches beyond the largest bucket:
        # past it, bucket_size degrades to exact-size rounding and every
        # new batch size triggers a fresh (minutes-long on TPU) compile
        self.max_batch = max(buckets)
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
            # per-step placement of the two input buffers: the vote rows
            # split over the vote axis, the slot state replicated — the
            # compiled programs see one canonical input layout per bucket
            # (zero-recompile across epoch restages, same as the
            # single-device ladder)
            self._put_shardings = (
                NamedSharding(self.mesh, PartitionSpec(VOTE_AXIS)),
                NamedSharding(self.mesh, PartitionSpec()),
            )
        else:
            self._n_shards = 1
            self._fn = tally.compact_step_packed_jit()
            self._put_shardings = None
        # sharded host-prep pool (engine.hostprep): sized by the FIRST
        # sizer — co-located engines sharing this verifier share one pool
        # (ensure_host_pool), so worker count doesn't multiply per node
        self._host_pool = None
        self.host_prep_workers = 0
        self.host_prep_backend = "thread"
        self._stats_mtx = make_lock("verifier.DeviceVoteVerifier._stats_mtx")
        # the steps' explicit host->device puts and their bytes (two a
        # step: vote rows and slot state; pipeline_stats()["h2d"])
        self.h2d_puts = 0
        self.h2d_bytes = 0
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
        # test and benchmark configs already compile for are their own pow2,
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

    def h2d_stats(self) -> dict:
        """The steps' host->device puts and bytes since construction."""
        with self._stats_mtx:
            return {"h2d_puts": self.h2d_puts, "h2d_bytes": self.h2d_bytes}

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
        compiled programs. Returns False when the new
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

        full=True additionally compiles the shapes loaded runs hit: the
        (batch-bucket, slot-bucket) combos (b, b) and (b, smallest) for
        every bucket b, the combos engine drains produce (slots = unique
        txs <= votes, so slot buckets other than the batch's own and the
        floor are rare). A shape left cold here compiles MID-RUN on the
        first batch that hits it, stalling the pipeline for the entire
        compile (r5 measured: a 169 s throughput phase containing ~160 s
        of one such compile)."""
        self.verify_and_tally(
            [b""] * n, [b""] * n, np.zeros(n, np.int64), np.zeros(n, np.int64), 1
        )
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
        the device computes this one."""
        n = len(msgs)
        val_idx = np.asarray(val_idx, dtype=np.int64)
        tx_slot = np.asarray(tx_slot, dtype=np.int32)
        keep = first_occurrence_mask(tx_slot, val_idx)
        st = self._stage  # one read: epoch-consistent tables/powers/quorum
        b = bucket_size(n, self.buckets, multiple=self._n_shards)
        # n_slots is a compiled shape too (prior_stake) — bucket it as well,
        # or every step with a new in-flight tx count would recompile the
        # whole kernel; padding slots receive no votes and slice away
        b_slots = bucket_size(n_slots, self.buckets)

        batch = ed25519_batch.prepare_compact(
            msgs, sigs, val_idx, st.epoch, pool=self._host_pool
        )
        batch.pre_ok &= keep
        q = st.val_set.quorum_power() if quorum is None else quorum
        rows, slot_state = tally.pack_step_inputs(
            batch, tx_slot, b, prior_stake, b_slots, q
        )

        self.shapes_used.add(("fused", b, b_slots))
        import jax

        # the step's two explicit host->device puts (one call): vote rows
        # split across the mesh (b is a multiple of _n_shards by
        # construction), slot state replicated; on one device both go to
        # the default device. Nothing else the program reads is on the
        # host: tables and powers are the stage's device arrays.
        rows, slot_state = jax.device_put((rows, slot_state), self._put_shardings)
        with self._stats_mtx:
            self.h2d_puts += 2
            self.h2d_bytes += rows.nbytes + slot_state.nbytes
        packed = self._fn(rows, st.tables_dev, st.powers_dev, slot_state)
        # ONE readback — deferred to ticket.result() and (with a staging
        # ring) already in flight on the ring thread; per-shard layout
        # [valid b/n | stake S | maj S] (tally.compact_step_packed);
        # stake/maj repeat the replicated global per shard — the ticket
        # takes shard 0's copy
        return _FusedDeviceTicket(
            self._stage_readback(packed), n, n_slots, self._n_shards, b,
            b_slots, keep,
        )

    def predicted_shapes(self, n: int, n_slots: int = 1) -> list[tuple]:
        """The (kind, batch-bucket, slot-bucket) shape an n-vote /
        n_slots-tx batch dispatches through this verifier — the
        cold-shape gate's input (engine.shapes.ShapeWarmRegistry
        .is_batch_warm): exactly what ``submit`` adds to ``shapes_used``."""
        shards = self._n_shards
        return [(
            "fused",
            bucket_size(n, self.buckets, multiple=shards),
            bucket_size(n_slots, self.buckets),
        )]


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
    in the counters here.

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
        if fallback is None:
            fallback = ScalarVoteVerifier(self.val_set)
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
        # observability (RPC and perfbench surface them; tests assert transitions)
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
        (keeping its compiled shapes and the degradation counters
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
            res = outer.verify_and_tally(
                msgs, sigs, val_idx, tx_slot, n_slots,
                prior_stake=prior, quorum=quorum,
            )
        else:
            outer._mark_device(True)
        self._done = res
        return res

