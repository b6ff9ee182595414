"""The node's schedule for Python's cycle collector.

CPython (3.12, ``Modules/gcmodule.c`` ``gc_collect_generations``) starts
a full collection once the oldest generation has seen ``threshold2``
middle collections AND the objects promoted since the last full one
reach a quarter of what survived it: every promoted object is paid for
with four objects walked, whatever the heap's size. A validator under a
vote flood promotes nearly everything it allocates (a vote lives for
seconds, through hundreds of young collections) and frees it by
reference count, never by the collector, so a quarter of its time went
to walking votes that were not garbage, and JAX's start-up heap beside
them.

While a node runs, the process instead:

* keeps what start-up built out of every collection (``gc.freeze()``
  after one ``gc.collect()``): frozen objects are still freed by
  reference count, the collector just stops walking them;
* starts a full collection only when the promotions since the last one
  can equal the tracked heap it left (survivors + frozen), not a
  quarter of it: after each full collection ``threshold2`` is
  re-derived from that count and what a middle collection promotes
  when nothing dies young, ``(threshold0 + 1) * (threshold1 + 1)``
  (an estimate, as the thresholds themselves are: the young count
  falls with every object freed by reference count). The stock quarter
  rule still has to hold, and the threshold found at install is the
  floor. Cyclic garbage can about double the process's tracked heap
  before it is walked.

Young collections (thresholds 0 and 1) are not touched. Nothing here is
a setting: the schedule follows what the last full collection observed.
One policy a process, counted like the tracer's ``_GcHook``: the first
node started installs it, the last one stopped restores the thresholds
it found and unfreezes.
"""

from __future__ import annotations

import gc
import threading

from .clock import monotonic

_FULL = 2  # the oldest generation


class CollectorPolicy:
    def __init__(self):
        self._mtx = threading.Lock()
        self._users = 0
        self._found = gc.get_threshold()
        self._t0 = 0.0
        self.full_collections = 0
        self.full_collect_s = 0.0
        self.survivors = 0  # unfrozen objects the last full collection left
        # counted once, at the freeze: gc.get_freeze_count() walks the
        # whole permanent generation (40-80 ns an object, 30 ms and more
        # on a started node's heap), and /health reads stats() every tick
        self.frozen = 0

    def install(self) -> None:
        with self._mtx:
            self._users += 1
            if self._users > 1:
                return
            self._found = gc.get_threshold()
            self.full_collections = 0
            self.full_collect_s = 0.0
            gc.collect()
            gc.freeze()
            self.frozen = gc.get_freeze_count()
            self._retune()
            # before the tracer's hook (node.py installs that next), so a
            # gc_pause span holds what the schedule costs. Appended, not
            # inserted: a collection running on another thread walks this
            # list by index
            self._t0 = 0.0
            gc.callbacks.append(self)

    def remove(self) -> None:
        with self._mtx:
            if self._users == 0:
                return
            self._users -= 1
            if self._users:
                return
            gc.callbacks.remove(self)
            gc.unfreeze()
            self.frozen = 0
            gc.set_threshold(*self._found)

    def __call__(self, phase: str, info: dict) -> None:
        # never takes _mtx: a collection can start at any allocation
        if info["generation"] != _FULL:
            return
        if phase == "start":
            self._t0 = monotonic()
        elif self._t0:  # not the tail of a collection that began before install
            self._retune()
            self.full_collections += 1
            self.full_collect_s += monotonic() - self._t0
            self._t0 = 0.0

    def _retune(self) -> None:
        t0, t1, _ = gc.get_threshold()
        self.survivors = len(gc.get_objects(generation=_FULL))
        heap = self.survivors + self.frozen
        gc.set_threshold(
            t0, t1, max(self._found[2], heap // ((t0 + 1) * (t1 + 1)))
        )

    def stats(self) -> dict:
        return {
            "full_collections": self.full_collections,
            "full_collect_s": round(self.full_collect_s, 4),
            "survivors": self.survivors,
            "frozen_objects": self.frozen,
        }


COLLECTOR = CollectorPolicy()
