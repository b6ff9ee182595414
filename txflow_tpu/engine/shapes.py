"""Shape-warm registry: every kernel shape the verify pipeline can hit.

The device verifier compiles one XLA program per (batch-bucket,
slot-bucket) shape, and a cold shape compiles MID-RUN on the first batch
that needs it — about a minute per shape for the TPU (the r5 bench
postmortem: one in-run compile buried a throughput phase almost
entirely under compile). With the pipelined engine the damage is worse:
a compile stalls the in-flight ticket AND every batch queued behind it.

``ShapeWarmRegistry`` closes the loop in four parts:

1. ``enumerate_shapes()`` — predict the (kind, batch-bucket, slot-bucket)
   shapes reachable from the verifier's configuration (mirrors
   ``DeviceVoteVerifier.warmup``'s coverage: the fused bucket combos);
2. ``prewarm()`` — run ``warmup(full=...)`` once and SNAPSHOT the shapes
   the verifier actually dispatched (``DeviceVoteVerifier.shapes_used``),
   which is the authoritative warm set;
3. ``shapes_for_batch()`` / ``is_batch_warm()`` / ``warm_shape()`` — the
   incremental surface the engine's background-warmup path uses: predict
   the shapes ONE batch can hit, check them against the warm set, and
   compile a single shape off the hot path (``BackgroundWarmer`` walks
   the enumeration smallest-first on its own thread while the engine
   serves cold-shape batches through the scalar fallback);
4. ``cold_shapes()`` / ``compile_in_run()`` — diff the shapes used since
   the snapshot against it, so a run can assert (perfbench and
   chip_smoke.py report the cold shapes of a run) that no compile
   contaminated the timed phase instead of silently eating it. Shapes
   compiled by the warmer count as warm, not as in-run compiles: the
   compile ran concurrently with serving, never inside a dispatch.

Wrapper verifiers (ResilientVoteVerifier, FlakyVerifier) are
unwrapped via their ``device``/``inner`` attributes; a scalar verifier has
no compiled shapes and degrades every query to the empty set (and every
batch to warm).
"""

from __future__ import annotations

import threading

import numpy as np

from ..verifier import DeviceVoteVerifier, bucket_size


def _unwrap_device(verifier) -> DeviceVoteVerifier | None:
    """Follow wrapper chains (.device / .inner) to the device verifier."""
    seen = set()
    v = verifier
    while v is not None and id(v) not in seen:
        if isinstance(v, DeviceVoteVerifier):
            return v
        seen.add(id(v))
        v = getattr(v, "device", None) or getattr(v, "inner", None)
    return None


class ShapeWarmRegistry:
    def __init__(self, verifier):
        self._verifier = verifier
        self.device = _unwrap_device(verifier)
        self.warmed: set[tuple] = set()
        # shapes a BackgroundWarmer is compiling RIGHT NOW: excluded from
        # cold_shapes (the dispatch is off the hot path by construction)
        # but NOT yet warm — the engine must keep routing batches of this
        # shape through the fallback or it would block on the same compile
        self._warming: set[tuple] = set()
        self._mtx = threading.Lock()

    def enumerate_shapes(self, n: int = 1, full: bool = True) -> list[tuple]:
        """Predicted (kind, batch-bucket, slot-bucket) set for a warmup(n,
        full) call — mirrors DeviceVoteVerifier.warmup's coverage."""
        dev = self.device
        if dev is None:
            return []
        shards = dev._n_shards
        shapes: set[tuple] = set()
        # warmup(n) compiles n's own combo; full=True adds
        # (b, b) and (b, smallest) for every bucket b
        shapes.add((
            "fused",
            bucket_size(n, dev.buckets, multiple=shards),
            bucket_size(1, dev.buckets),
        ))
        if full:
            smallest = dev.buckets[0]
            for b in dev.buckets:
                bb = bucket_size(b, dev.buckets, multiple=shards)
                shapes.add(("fused", bb, bucket_size(b, dev.buckets)))
                shapes.add(("fused", bb, smallest))
        return sorted(shapes)

    def shapes_for_batch(self, n: int, n_slots: int = 1) -> list[tuple]:
        """The shape ONE n-vote / n_slots-tx batch dispatches: exactly one
        fused (batch-bucket, slot-bucket) combo."""
        dev = self.device
        if dev is None:
            return []
        return dev.predicted_shapes(n, n_slots)

    def is_warm(self, shape: tuple) -> bool:
        with self._mtx:
            return shape in self.warmed

    def is_batch_warm(self, n: int, n_slots: int = 1) -> bool:
        """True when every shape an n-vote batch can hit is compiled —
        the engine's cold-shape gate: a False routes the batch through
        the scalar fallback instead of stalling on a compile."""
        dev = self.device
        if dev is None:
            return True
        needed = self.shapes_for_batch(n, n_slots)
        with self._mtx:
            return all(s in self.warmed for s in needed)

    def mark_warm(self, shapes) -> None:
        with self._mtx:
            self.warmed.update(shapes)

    def warm_shape(self, shape: tuple) -> bool:
        """Compile one enumerated shape by dispatching a throwaway batch
        of exactly that shape (BackgroundWarmer thread; safe concurrently
        with serving — JAX compiles under its own locks while the engine
        keeps dispatching already-warm programs). Returns True when the
        shape is warm on return."""
        dev = self.device
        if dev is None:
            return False
        _, b, b_slots = shape
        with self._mtx:
            if shape in self.warmed:
                return True
            self._warming.add(shape)
        seen_before = shape in dev.shapes_used
        try:
            nn = _generating_size(b, dev.buckets, dev._n_shards)
            # slot buckets are not shard-rounded: b_slots IS a bucket
            dev.verify_and_tally(
                [b""] * nn, [b""] * nn,
                np.zeros(nn, np.int64), np.zeros(nn, np.int64),
                b_slots,
            )
        except Exception:
            with self._mtx:
                self._warming.discard(shape)
            if not seen_before:
                # a failed dispatch must not read as an in-run compile
                dev.shapes_used.discard(shape)
            return False
        with self._mtx:
            self._warming.discard(shape)
            self.warmed.add(shape)
        return True

    def prewarm(self, n: int = 1, full: bool = True) -> list[tuple]:
        """Compile every reachable shape once (delegates to the verifier's
        own warmup so wrapper policies apply) and snapshot the warm set."""
        warm = getattr(self._verifier, "warmup", None)
        if warm is not None:
            warm(n, full=full)
        if self.device is not None:
            with self._mtx:
                self.warmed |= _copy_shape_set(self.device.shapes_used)
        return sorted(self.warmed)

    def cold_shapes(self) -> list[tuple]:
        """Shapes dispatched since prewarm that were NOT in the warm
        snapshot (and are not mid-compile on the warmer thread) — each
        one was an in-run compile on the hot path."""
        if self.device is None:
            return []
        used = _copy_shape_set(self.device.shapes_used)
        with self._mtx:
            return sorted(used - self.warmed - self._warming)

    def compile_in_run(self) -> bool:
        return bool(self.cold_shapes())


def _generating_size(b: int, buckets, shards: int) -> int:
    """Largest raw batch size n with bucket_size(n, buckets, shards) == b.

    warm_shape must dispatch the PADDED bucket width b via a raw n that
    maps to it — calling with n=b directly would round b (already
    shard-rounded past its bucket) up to the NEXT bucket and compile the
    wrong shape (e.g. bucket 64 on a 6-shard mesh pads to 66; a 66-vote
    probe would land on the 256 bucket)."""
    for bb in sorted(buckets, reverse=True):
        if ((bb + shards - 1) // shards) * shards == b:
            return bb
    return b


def _copy_shape_set(s: set) -> set:
    """Snapshot a set another thread may be growing (shapes_used).

    The verifier's ``_ShapeSet`` takes its lock in ``snapshot()`` for a
    consistent copy; the retry loop remains as a fallback for plain sets
    (tests hand in bare ``set()`` doubles), where a concurrent resize can
    raise RuntimeError mid-iteration — new shapes are rare (one per
    first-dispatch), so a short retry always wins."""
    snap = getattr(s, "snapshot", None)
    if snap is not None:
        return snap()
    for _ in range(8):
        try:
            return set(s)
        except RuntimeError:
            continue
    return set(s)


class BackgroundWarmer:
    """Compile cold shapes on a side thread while the engine serves.

    The zero→warm path without a blocking prewarm: the engine starts
    serving immediately, batches whose shape is still cold route through
    the scalar fallback (TxFlow._submit_prep), and this thread walks
    ``enumerate_shapes(full=True)`` smallest-first compiling each cold
    shape via ``ShapeWarmRegistry.warm_shape``. When a shape lands, the
    gate flips and the engine PROMOTES batches of that shape to the
    device — promotion, never a hot-path stall. With the persistent
    compilation cache (utils.compile_cache) the walk is a cache load on
    every run after the first."""

    def __init__(self, registry: ShapeWarmRegistry, full: bool = True, n: int = 1):
        self.registry = registry
        self.full = full
        self.n = n
        self.compiled = 0
        self.failed = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        if self._thread is not None or self.registry.device is None:
            return
        self._thread = threading.Thread(
            target=self._run, name="txflow-shape-warmup", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        reg = self.registry
        # smallest-first: small shapes compile fastest and cover the
        # light-load batches that arrive first, so promotion starts early
        for shape in reg.enumerate_shapes(self.n, full=self.full):
            if self._stop.is_set():
                return
            if reg.is_warm(shape):
                continue
            if reg.warm_shape(shape):
                self.compiled += 1
            else:
                self.failed += 1

    def done(self) -> bool:
        t = self._thread
        return t is not None and not t.is_alive()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
