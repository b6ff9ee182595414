"""Traffic kind ``flood``: pre-signed votes replayed at the vote pool's
ingest, closed on a bounded backlog.

The feeder tops the node up in chunks of ``chunk_txs`` whenever the txs fed
but not yet committed fall below ``backlog_txs``, and never lets it run
dry: a real peer's send queue is bounded, and an unbounded feeder measures
how far it got ahead. Within a chunk the txs are seeded into the mempool
and then each validator's votes are delivered as one frame from that
validator, in validator order (go-txflow's "pregenerated TxVotes replayed
through txvotepool").
"""

from __future__ import annotations

import threading
import time


class BacklogFeeder:
    """The backlog rule, apart from any node. ``feed(lo, hi)`` delivers
    txs [lo, hi); ``committed()`` counts commits so far."""

    def __init__(self, backlog_txs: int, chunk_txs: int, n_txs: int, feed, committed):
        if backlog_txs < chunk_txs or chunk_txs <= 0:
            raise ValueError("backlog_txs must hold at least one chunk")
        self.backlog_txs, self.chunk_txs, self.n_txs = backlog_txs, chunk_txs, n_txs
        self._feed, self._committed = feed, committed
        self.fed = 0
        self.max_outstanding = 0
        self.min_outstanding_before_feed: int | None = None
        self.exhausted = False

    def outstanding(self) -> int:
        return self.fed - self._committed()

    def _feed_chunk(self) -> bool:
        if self.fed + self.chunk_txs > self.n_txs:
            self.exhausted = True
            return False
        self._feed(self.fed, self.fed + self.chunk_txs)
        self.fed += self.chunk_txs
        return True

    def pump(self, stop=None) -> int:
        """Feed chunks until the backlog is back at its mark (or ``stop()``
        says to leave off). Returns the chunks fed."""
        fed = 0
        while True:
            out = self.outstanding()
            if out >= self.backlog_txs or (stop is not None and stop()):
                return fed
            if self.fed:  # the first fill starts from nothing by design
                low = self.min_outstanding_before_feed
                self.min_outstanding_before_feed = out if low is None else min(low, out)
            if not self._feed_chunk():
                return fed
            fed += 1
            self.max_outstanding = max(self.max_outstanding, out + self.chunk_txs)

    def align(self, multiple: int) -> None:
        """At the close: round what was fed up to a multiple, so that the
        tail drains as whole rungs and reaches no program the cell did not
        warm."""
        while self.fed % multiple and self._feed_chunk():
            pass


class FeederThread(threading.Thread):
    def __init__(self, feeder: BacklogFeeder, wake: threading.Event):
        super().__init__(name="flood-feeder", daemon=True)
        self.feeder = feeder
        self._wake = wake
        self._halt = threading.Event()
        self.error: BaseException | None = None
        self.feeds: list[tuple[float, int]] = []  # (time, chunks)

    def run(self) -> None:
        try:
            while not self._halt.is_set():
                n = self.feeder.pump(self._halt.is_set)
                if n:
                    self.feeds.append((time.monotonic(), n))
                self._wake.wait(0.005)
                self._wake.clear()
        except BaseException as e:  # surfaced by the driver, never swallowed
            self.error = e

    def halt(self) -> None:
        self._halt.set()
        self._wake.set()
        self.join(timeout=30)
        if self.is_alive():
            raise RuntimeError("the feeder did not stop")
