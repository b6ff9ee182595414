"""Fixed-size LRU set (reference txvotepool ``mapTxCache``, :388-451).

push() returns False when the key is already cached — refreshing its
recency, exactly like the reference's Push (list.MoveToBack before the
false return) — and at capacity the least-recently-pushed entry is
evicted, exactly one. The container is a ``collections.OrderedDict``
(``move_to_end`` to refresh, ``popitem(last=False)`` to evict): both are
constant-time at any fill. The plain dict this file used between r5 and
PR 29 was cheaper per push only while a set never filled; once it did,
``del m[next(iter(m))]`` walked the dead slots that earlier evictions
had left at the front of the dict's entry array, tens of thousands
between two resizes, and cost the flood over a third of its rate (PERF.md
section 6, PR 29). The dedup sets fill within seconds and evict on every
push from then on, and the hot pools pay a push per ingest, so the steady
state is the case that counts.

Thread safety, for every class here: mutations belong to the owner's
lock (the set's own in ``LRUCache`` / ``LRUMap``, the owner's mutex for
``UnlockedLRUCache``); the only operations other threads run without it
are ``in`` on a set and ``LRUMap.peek``, both the C-level ``dict``
lookup that ``OrderedDict`` inherits, atomic under the GIL (see
``UnlockedLRUCache``).
"""

from __future__ import annotations

import functools
import sys
import threading
from collections import OrderedDict


def _gil_enabled() -> bool:
    """True unless this is a free-threaded (PEP 703) build running with
    the GIL actually disabled. sys._is_gil_enabled only exists on
    free-threaded builds (3.13+); its absence means a GIL build."""
    probe = getattr(sys, "_is_gil_enabled", None)
    if probe is None:
        return True
    try:
        return bool(probe())
    except Exception:
        return True


# the GIL is a property of the build + interpreter launch options, not of
# any one call site: weigh it once
_GIL_ENABLED = _gil_enabled()


class _LRU:
    """The file's one LRU core: an ``OrderedDict`` oldest-first, a
    capacity, and the ONE place where recency and eviction are decided
    (``_admit``). Every step is constant-time whatever the fill and
    however long the set has been evicting: ``move_to_end`` relinks a
    node, ``popitem(last=False)`` unlinks the head."""

    def __init__(self, size: int):
        if size <= 0:
            raise ValueError("cache size must be positive")
        self.size = size
        self.evictions = 0  # keys pushed out at capacity, ever (health/registry.py)
        self._map: OrderedDict[bytes, object] = OrderedDict()

    def _admit(self, key: bytes) -> bool:
        """Add key, or refresh it (MoveToBack); False if it was present.
        At capacity exactly one key goes, the least recently admitted."""
        m = self._map
        if key in m:
            m.move_to_end(key)
            return False
        if len(m) >= self.size:
            m.popitem(last=False)
            self.evictions += 1
        m[key] = None
        return True


class UnlockedLRUCache(_LRU):
    """The LRU set without a lock, for owners that already serialize
    every MUTATION under their own mutex (both pools mutate their dedup
    caches exclusively under the pool lock; the engine's committed-set
    under the engine lock; the admission dedup under the controller's).

    What other threads may run WITHOUT that mutex is ``in`` alone
    (``TxVotePool.in_cache``, the engine's ``_committed.__contains__``).
    ``OrderedDict`` inherits ``__contains__`` from ``dict``: a C-level
    hash lookup over bytes keys that reads the dict's table and never the
    order list that ``move_to_end`` / ``popitem`` relink, runs no Python
    code (bytes hash and compare in C) and so cannot lose the GIL half
    way: a reader never observes a table mid-resize or mid-insert. Its
    answer may be stale by one racing push, which the callers tolerate
    by falling back to the authoritative check_tx path (``LRUMap.peek``
    states the same for ``get``). ``len`` is as safe; ``push``,
    ``remove`` and ``reset`` are the owner's, under its mutex: two
    unserialized pushes could both evict.

    The argument is CPython-specific and GIL-specific. It does NOT hold
    on free-threaded (PEP 703) builds, where an unsynchronized reader
    racing a push is genuine undefined behavior. On such builds (weighed
    once at import, ``_GIL_ENABLED``) the constructor transparently
    returns a locked ``LRUCache`` instead — every call site keeps its
    semantics and pays the lock only where the GIL no longer provides
    it."""

    def __new__(cls, size: int):
        if not _GIL_ENABLED:
            return LRUCache(size)
        return object.__new__(cls)

    # push(key) -> bool: False if already present (recency refreshed)
    push = _LRU._admit

    def remove(self, key: bytes) -> None:
        self._map.pop(key, None)

    def reset(self) -> None:
        self._map.clear()

    def __contains__(self, key: bytes) -> bool:
        return key in self._map

    def __len__(self) -> int:
        return len(self._map)


def _locked(op):
    @functools.wraps(op)
    def locked(self, *args):
        with self._mtx:
            return op(self, *args)

    return locked


class LRUCache(_LRU):
    """UnlockedLRUCache's five operations, each under the set's own lock:
    the lock is the only difference between the two classes."""

    def __init__(self, size: int):
        super().__init__(size)
        self._mtx = threading.Lock()

    push = _locked(UnlockedLRUCache.push)
    remove = _locked(UnlockedLRUCache.remove)
    reset = _locked(UnlockedLRUCache.reset)
    __contains__ = _locked(UnlockedLRUCache.__contains__)
    __len__ = _locked(UnlockedLRUCache.__len__)


def make_lru(size: int):
    """The one construction seam for dedup caches — and the ONE place
    that weighs the CPython/GIL safety argument (checked once at import,
    module constant below). txlint's ``unlocked-lru`` rule forbids
    constructing UnlockedLRUCache directly anywhere else.

    size <= 0 means "cache disabled" (NopCache), matching the pools'
    config.cache_size contract. On GIL builds the owner-serialized
    lock-free cache is returned; on free-threaded builds every caller
    transparently gets the locked LRUCache instead."""
    if size <= 0:
        return NopCache()
    if _GIL_ENABLED:
        return UnlockedLRUCache(size)
    return LRUCache(size)


class NopCache:
    """Cache disabled (config.cache_size = 0): everything is new."""

    evictions = 0

    def push(self, key: bytes) -> bool:
        return True

    def remove(self, key: bytes) -> None:
        pass

    def reset(self) -> None:
        pass

    def __contains__(self, key: bytes) -> bool:
        return False

    def __len__(self) -> int:
        return 0


class LRUMap(_LRU):
    """Fixed-size LRU key->value map (wire-segment dedup in the reactors)."""

    def __init__(self, size: int):
        super().__init__(size)
        self._mtx = threading.Lock()

    def get(self, key: bytes):
        with self._mtx:
            v = self._map.get(key)
            if v is not None:
                self._map.move_to_end(key)
            return v

    def peek(self, key: bytes):
        """Lock-free read with NO recency update. OrderedDict.get is the
        C-level dict lookup, atomic under the GIL, and concurrent put/
        evict mutations cannot corrupt a reader — worst case a racing
        peek misses a value another thread is inserting, which every
        caller must treat as a cache miss anyway. The hot gossip receive
        path peeks (12 reader threads at bench rates); recency then only
        advances on put, making eviction FIFO-ish for peek-heavy maps —
        fine for dedup caches."""
        return self._map.get(key)

    def put(self, key: bytes, value) -> None:
        with self._mtx:
            self._admit(key)  # a racing peek may read None: a miss, as ever
            self._map[key] = value
