"""EventBus: in-process pubsub (reference: tendermint libs/pubsub EventBus).

The fast path publishes per-tx commit events (txflowstate/execution.go:
190-195) and the block path publishes NewBlock/NewRound/validator-set
events (state/execution.go:456-481) to RPC websocket subscribers and the
tx indexer. Here: typed event names, thread-safe subscribe with per-
subscriber queues (non-blocking publish drops to slow subscribers beyond
capacity, like pubsub's buffered channels).
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass, field
from typing import Callable

# event types (reference types/events.go)
EventTx = "Tx"
EventNewBlock = "NewBlock"
EventNewBlockHeader = "NewBlockHeader"
EventNewRound = "NewRound"
EventNewRoundStep = "NewRoundStep"
EventCompleteProposal = "CompleteProposal"
EventVote = "Vote"
EventValidatorSetUpdates = "ValidatorSetUpdates"
EventEvidence = "Evidence"  # equivocation captured (types/evidence.py)


@dataclass
class Event:
    type: str
    data: object = None
    # open txtrace ``publish`` span of a sampled commit event (0 = none):
    # whoever hands the event's frame to a subscriber's socket finishes it
    span: int = 0


class Subscription:
    def __init__(self, capacity: int = 1000):
        self._q: queue.Queue[Event] = queue.Queue(maxsize=capacity)
        self._mtx = threading.Lock()
        self._closed = False

    def deliver(self, ev: Event) -> bool:
        with self._mtx:
            if self._closed:
                return False
            try:
                self._q.put_nowait(ev)
                return True
            except queue.Full:
                return False

    def close(self) -> list[Event]:
        """Refuse further deliveries and hand back what was never read
        (a publish that copied the subscriber list before the
        unsubscribe cannot slip an event in after this)."""
        with self._mtx:
            self._closed = True
        return self.drain()

    def get(self, timeout: float | None = None) -> Event | None:
        try:
            return self._q.get(timeout=timeout)
        except queue.Empty:
            return None

    def drain(self) -> list[Event]:
        out = []
        while True:
            try:
                out.append(self._q.get_nowait())
            except queue.Empty:
                return out


class EventBus:
    def __init__(self):
        self._mtx = threading.Lock()
        self._subs: dict[str, list[Subscription]] = {}
        self._callbacks: dict[str, list[Callable[[Event], None]]] = {}

    def subscribe(self, event_type: str, capacity: int = 1000) -> Subscription:
        sub = Subscription(capacity)
        with self._mtx:
            self._subs.setdefault(event_type, []).append(sub)
        return sub

    def subscribe_callback(self, event_type: str, fn: Callable[[Event], None]) -> None:
        with self._mtx:
            self._callbacks.setdefault(event_type, []).append(fn)

    def unsubscribe(self, event_type: str, sub: Subscription) -> None:
        with self._mtx:
            subs = self._subs.get(event_type, [])
            if sub in subs:
                subs.remove(sub)

    def publish(self, event_type: str, data: object = None, span: int = 0) -> int:
        """Returns how many subscriber queues took the event."""
        ev = Event(event_type, data, span)
        with self._mtx:
            subs = list(self._subs.get(event_type, []))
            cbs = list(self._callbacks.get(event_type, []))
        taken = sum(1 for s in subs if s.deliver(ev))
        for cb in cbs:
            cb(ev)
        return taken


@dataclass
class EventDataTx:
    """Per-tx commit event payload (reference types.EventDataTx)."""

    height: int
    tx: bytes
    tx_hash: str
    result_code: int = 0
    result_data: bytes = b""
    result_log: str = ""
    tags: list = field(default_factory=list)  # (key, value) byte pairs


@dataclass
class EventDataNewBlock:
    block: object = None


@dataclass
class EventDataValidatorSetUpdates:
    updates: list = field(default_factory=list)
