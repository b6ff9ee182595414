"""Shared pool plumbing: ingest log, seq wakeups, cursor walks.

Both pools (mempool, txvotepool) expose the same consumer protocol:

- ``seq()`` / ``wait_for_new(last_seq, timeout)`` — a monotonic ingest
  counter with condition-variable wakeups (the CList TxsWaitChan analog);
- ``entries_from(cursor, limit)`` — a stable-cursor walk over the ingest
  log (the CList pointer-walk analog, reference txvotepool/reactor.go:
  198-265): removals never shift a cursor.

The log is append-only but COMPACTED: once enough removed ("dead") keys
accumulate at its head, the dead prefix is dropped and a base offset
advances. Cursors are absolute positions, so a walker behind the new base
resumes at the base — it only skips entries that were already dead, which
the walk would have skipped anyway. This bounds memory where the naive
log grows forever at fast-path vote rates (the reference's CList frees
nodes once all walkers pass; this is the batched equivalent).
"""

from __future__ import annotations

import threading

from ..analysis.lockgraph import make_rlock
from ..analysis.racegraph import shared_field
from ..utils.clock import monotonic

# Compact when at least this many dead entries can be dropped at once.
COMPACT_THRESHOLD = 4096


class IngestLogPool:
    """Mixin-style base: subclasses store live items in ``self._items``
    (an insertion-ordered dict keyed by bytes) and call ``_log_append`` on
    accept / ``_log_compact`` after bulk removals, all under ``self._mtx``."""

    def __init__(self):
        self._mtx = make_rlock(f"pool.{type(self).__name__}._mtx")
        self._cond = threading.Condition(self._mtx)
        self._seq = 0
        self._log: list[bytes] = []
        self._log_base = 0  # absolute position of _log[0]
        self._items: dict[bytes, object] = {}
        # when the first item since the consumer's last take_first_new()
        # was appended (0 = none since): the start of the engine's
        # pickup_wait span, which so holds the rest of that frame's
        # ingest too
        self._first_new_t = 0.0
        # the ingest log + entry map, every reactor walk and engine drain
        # crosses threads through them
        self._sh_log = shared_field(f"pool.{type(self).__name__}.ingest_log")  # txlint: shared(self._mtx)

    # -- ingest bookkeeping (call under self._mtx) --

    def _log_append(self, key: bytes) -> None:
        self._sh_log.note_write()
        self._log.append(key)
        self._seq += 1
        if not self._first_new_t:
            self._first_new_t = monotonic()
        self._cond.notify_all()

    def _log_append_quiet(self, key: bytes) -> None:
        """Append WITHOUT waking waiters — batch ingest paths append a
        whole lock-group and then call _log_notify once (a notify_all per
        vote measured as ~1/3 of the ingest cost, r5 microbench). Callers
        MUST follow with _log_notify before releasing the lock, or
        waiters sleep a full poll interval past available work."""
        self._sh_log.note_write()
        self._log.append(key)
        self._seq += 1
        if not self._first_new_t:
            self._first_new_t = monotonic()

    def _log_extend_quiet(self, keys: list[bytes]) -> None:
        """_log_append_quiet for a whole lock-group's keys at once, in
        their ingest order. The caller stamps _first_new_t itself, when
        the group's first item is accepted, and follows with _log_notify."""
        self._sh_log.note_write()
        self._log.extend(keys)
        self._seq += len(keys)

    def _log_notify(self) -> None:
        self._cond.notify_all()

    def take_first_new(self) -> float:
        """When the first item since the last call was accepted (0.0 =
        none since), and start over: the consumer calls it as it drains."""
        with self._mtx:
            t, self._first_new_t = self._first_new_t, 0.0
        return t

    def _log_compact(self) -> None:
        """Drop the longest dead prefix once it crosses the threshold.

        Amortized O(1) per removal: the (O(prefix)) scan only runs when the
        log has at least COMPACT_THRESHOLD more entries than live items —
        scanning from 0 on EVERY bulk removal measured at 0.9 ms/call with
        a 16k-vote log (r3 step profile), serializing the commit path."""
        self._sh_log.note_write()
        log = self._log
        items = self._items
        if len(log) - len(items) < COMPACT_THRESHOLD:
            return
        n = 0
        while n < len(log) and log[n] not in items:
            n += 1
        if n >= COMPACT_THRESHOLD:
            del log[:n]
            self._log_base += n

    # -- consumer protocol --

    def seq(self) -> int:
        """Monotonic ingest counter; pairs with wait_for_new."""
        with self._mtx:
            return self._seq

    def wait_for_new(self, last_seq: int, timeout: float) -> int:
        """Block until an item arrives after last_seq (or timeout); returns
        the current seq. Fires on EVERY accepted item (consumers idle here
        instead of polling)."""
        with self._cond:
            if self._seq == last_seq:
                self._cond.wait(timeout)
            return self._seq

    def _entries_from(self, cursor: int, limit: int):
        """(list of (key, item), new_cursor): live entries only, in ingest
        order, from an absolute cursor. Call paths wrap this to shape the
        item tuple."""
        out = []
        with self._mtx:
            self._sh_log.note_read()
            pos = max(cursor, self._log_base)
            while pos - self._log_base < len(self._log) and len(out) < limit:
                key = self._log[pos - self._log_base]
                item = self._items.get(key)
                if item is not None:
                    out.append((key, item))
                pos += 1
        return out, pos
