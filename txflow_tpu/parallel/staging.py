"""Double-buffered device readback: overlap batch N-1's D2H with batch N.

The verify pipeline's one blocking host<->device synchronization is the
packed-result readback (``np.asarray(packed)`` in the ticket's
``result()``). Serially that readback sits BETWEEN steps: the engine
cannot stage batch N (device_put + dispatch) until batch N-1's bytes have
crossed back. A ``StagingRing`` breaks that ordering: every dispatched
device array enters the ring, a dedicated readback thread pulls it to
host EAGERLY (device->host DMA overlapping whatever the caller does
next), and the ticket's ``result()`` waits on the slot instead of issuing
the transfer itself. With depth 2 — classic double buffering (see the
Pallas guide's double-buffer pattern for the on-chip analog) — batch N's
staging runs while batch N-1's readback is in flight.

Correctness envelope:

- **Byte parity is structural.** The ring changes WHERE ``np.asarray``
  runs, never what it reads: the same device array yields the same host
  bytes from any thread, and tickets are still collected in submission
  order by the engine. Certificates stay byte-identical to the scalar
  golden path (pinned by tests/test_staging_ring.py).
- **Bounded in-flight, never blocking.** A counting semaphore caps
  un-awaited slots at ``depth``; a submit past the cap runs its readback
  synchronously on the caller (accounted as ``sync_readbacks``) instead
  of waiting for a permit. Blocking would deadlock engines that share
  the ring: each fills `pipeline_depth` batches ahead of its collector
  on ONE loop thread, so when every permit holder is itself parked in
  ``submit``, the ``result()`` calls that release permits never run.
  Degrading keeps buffers bounded and costs only that batch's overlap.
- **Errors surface at the waiter.** A readback that raises (device OOM,
  backend teardown) is captured in the slot and re-raised from
  ``wait()`` — the thread never dies with the error, and the engine's
  drain-on-stop still settles every slot.

The ``hidden_s`` stat is the headline: readback seconds that ran while
the caller was NOT blocked in ``wait()`` — the time double-buffering
actually removed from the critical path (trace/report.py shows it as
``readback_overlap_hidden``).
"""

from __future__ import annotations

import threading

import numpy as np

from ..analysis.lockgraph import make_lock
from ..analysis.racegraph import shared_field
from ..utils.clock import monotonic


class StageSlot:
    """One in-flight readback: device array in, host array (or error) out.

    No lock guards the buffer fields: ownership moves caller -> readback
    thread -> waiter, with the ring queue (under ``_q_mtx``) and the
    ``_done`` Event's set()/wait() pair as the happens-before edges. The
    race auditor sees this as sanctioned handoffs, not a lockset."""

    __slots__ = (
        "_dev", "_host", "_error", "_done", "readback_s", "ready_t",
        "_waited", "_queued", "_sh",
    )

    def __init__(self, dev):
        self._dev = dev
        self._host = None
        self._error: BaseException | None = None
        self._done = threading.Event()
        self.readback_s = 0.0
        # when the bytes were usable on the host, read on the thread that
        # fetched them once it holds the interpreter lock again: the end
        # of the engine's device_busy span for this step
        self.ready_t: float | None = None
        self._waited = False
        self._queued = False
        self._sh = shared_field("parallel.StageSlot.buffer")  # txlint: shared(handoff)
        self._sh.note_write()

    def _run(self) -> None:
        t0 = monotonic()
        self._sh.note_write()
        try:
            self._host = np.asarray(self._dev)
        except BaseException as exc:  # re-raised at wait()
            self._error = exc
        finally:
            self._dev = None  # drop the device ref as soon as bytes land
            self.ready_t = monotonic()
            self.readback_s = self.ready_t - t0
            self._sh.handoff(
                "Event set()/wait() is the happens-before edge to the waiter"
            )
            self._done.set()

    def wait(self):
        """Block until the readback lands; returns the host array."""
        self._done.wait()
        self._sh.note_read()
        if self._error is not None:
            raise self._error
        return self._host


class StagingRing:
    """Depth-bounded readback ring with one eager readback thread.

    One ring per device verifier (it serializes D2H transfers in
    submission order, which is also the transfer-engine's natural
    order); all engines sharing the verifier share the ring. ``close()``
    drains the queue so every submitted slot still completes — stopping
    an engine never abandons an in-flight readback.
    """

    def __init__(self, depth: int = 2, name: str = "staging"):
        self.depth = max(1, int(depth))
        self._sem = threading.Semaphore(self.depth)
        self._q: list[StageSlot | None] = []
        self._q_mtx = make_lock("parallel.StagingRing._q_mtx")
        self._q_cv = threading.Condition(self._q_mtx)
        self._stats_mtx = make_lock("parallel.StagingRing._stats_mtx")
        # queue + in-flight count: submitters, waiters, and the readback
        # thread all cross here
        self._sh_q = shared_field("parallel.StagingRing.queue")  # txlint: shared(self._q_mtx)
        self._sh_stats = shared_field("parallel.StagingRing.stats")  # txlint: shared(self._stats_mtx)
        self._closed = False
        self.slots_total = 0
        self.readback_s = 0.0
        self.result_wait_s = 0.0
        self.hidden_s = 0.0
        self.sync_readbacks = 0
        self._in_flight = 0
        self._thread = threading.Thread(
            target=self._loop, name=f"{name}-readback", daemon=True
        )
        self._thread.start()

    def submit(self, dev) -> StageSlot:
        """Enter a device array into the ring; returns its slot.

        NEVER blocks: with ``depth`` earlier slots still un-awaited (or
        the ring closed) the readback runs synchronously on the caller
        instead — permits are released by ``result()``, and the engines
        sharing the ring each fill ahead of their own collector on one
        loop thread, so a blocking acquire here can park every
        permit-holder at once (deadlock)."""
        if not self._sem.acquire(blocking=False):
            # ring full: this batch forgoes overlap, buffers stay bounded
            return self._sync_slot(dev, fallback=True)
        slot = StageSlot(dev)
        with self._q_cv:
            if self._closed:
                self._sem.release()
                # closed: degrade to a synchronous slot so the caller
                # still gets its bytes (drain path, never lossy)
                return self._sync_slot(dev, fallback=False)
            slot._queued = True
            slot._sh.handoff(
                "queued under _q_mtx; readback thread is sole owner "
                "until _done.set()"
            )
            self._sh_q.note_write()
            self._q.append(slot)
            self._in_flight += 1
            self._q_cv.notify()
        with self._stats_mtx:
            self._sh_stats.note_write()
            self.slots_total += 1
        return slot

    def _sync_slot(self, dev, *, fallback: bool) -> StageSlot:
        slot = StageSlot(dev)
        slot._run()
        with self._stats_mtx:
            self._sh_stats.note_write()
            self.slots_total += 1
            self.readback_s += slot.readback_s
            if fallback:
                self.sync_readbacks += 1
        return slot

    def result(self, slot: StageSlot):
        """Wait on a slot with overlap accounting; returns the host array.

        The hidden-overlap ledger: a slot whose readback took ``d``
        seconds while the caller blocked here only ``w`` seconds had
        ``max(d - w, 0)`` of its transfer hidden behind caller work —
        with a synchronous readback the caller would have eaten all of
        ``d`` on the critical path."""
        t0 = monotonic()
        try:
            host = slot.wait()
        finally:
            w = monotonic() - t0
            release = False
            with self._q_mtx:
                self._sh_q.note_write()
                if slot._queued and not slot._waited:
                    slot._waited = True
                    self._in_flight -= 1
                    release = True
            if release:
                # synchronous slots hold no permit and were accounted at
                # submit (their readback ran ON the caller: nothing hidden)
                self._sem.release()
                with self._stats_mtx:
                    self._sh_stats.note_write()
                    self.result_wait_s += w
                    self.readback_s += slot.readback_s
                    self.hidden_s += max(slot.readback_s - w, 0.0)
        return host

    def _loop(self) -> None:
        while True:
            with self._q_cv:
                while not self._q and not self._closed:
                    self._q_cv.wait()
                if not self._q and self._closed:
                    return
                self._sh_q.note_write()
                slot = self._q.pop(0)
            if slot is None:
                return
            slot._run()

    def stats(self) -> dict:
        with self._stats_mtx, self._q_mtx:
            self._sh_stats.note_read()
            self._sh_q.note_read()
            return {
                "depth": self.depth,
                "slots_total": self.slots_total,
                "readback_s": self.readback_s,
                "result_wait_s": self.result_wait_s,
                "hidden_s": self.hidden_s,
                "sync_readbacks": self.sync_readbacks,
                "in_flight": self._in_flight,
            }

    def close(self, timeout: float = 5.0) -> None:
        """Stop the readback thread after draining queued slots.

        Slots already submitted still complete (their waiters may be
        other engines mid-collect); new submits degrade to synchronous
        readback. Idempotent."""
        with self._q_cv:
            if self._closed:
                return
            self._sh_q.note_write()
            self._closed = True
            self._q_cv.notify_all()
        self._thread.join(timeout=timeout)
