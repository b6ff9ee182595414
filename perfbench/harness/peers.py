"""The peers of a served cell: the other validators, played by the harness.

Their pre-signed votes for tx i reach the node's vote pool a delay after
tx i is due. ``peer_delay_ms`` in the traffic is one number for all of them
or a list of ``validators`` numbers, by validator index (the entry of the
node's own validator is not used: the node signs its own vote). The votes
of the peers that share a delay travel in one frame, signers in validator
order, so one number gives the one frame of n-1 votes. Everything here is
made in set-up; the injector's loop sleeps, stamps and delivers.
"""

from __future__ import annotations

import threading
import time

from . import client


def delays_of(traffic: dict, n_vals: int) -> list[float]:
    """The delay of every validator's votes in ms, by validator index."""
    delay = traffic["peer_delay_ms"]
    delays = [delay] * n_vals if isinstance(delay, (int, float)) else list(delay)
    if len(delays) != n_vals:
        raise ValueError(f"peer_delay_ms lists {len(delays)} validators, the cell has {n_vals}")
    if any(isinstance(d, bool) or not isinstance(d, (int, float)) or d < 0 for d in delays):
        raise ValueError(f"peer_delay_ms has to be numbers of 0 or more: {delay!r}")
    return [float(d) for d in delays]


def frames(delays_ms: list[float], signer_idx: list[int]) -> list[tuple[float, list]]:
    """[(delay in ms, [(place in the corpus, validator), ...]), ...] by
    rising delay: one frame for each distinct delay among the signers, the
    signers inside a frame in the corpus's (validator) order."""
    by_delay: dict[float, list] = {}
    for k, v in enumerate(signer_idx):
        by_delay.setdefault(delays_ms[v], []).append((k, v))
    return sorted(by_delay.items())


def quorum_delay_ms(powers: list[int], delays_ms: list[float], own: int | None) -> float:
    """The delay at which the stake delivered for a tx first passes 2/3 of
    the total: the node's own vote first (validator ``own``; None where the
    node does not sign), then the frames in order. It names where the path
    of the votes that complete the quorum starts, taking every delivered
    vote as valid; with one delay for every peer it is that delay."""
    quorum = sum(powers) * 2 // 3 + 1
    stake = 0 if own is None else powers[own]
    signers = [v for v in range(len(powers)) if v != own]
    if stake >= quorum or not signers:
        return 0.0
    for delay, group in frames(delays_ms, signers):
        stake += sum(powers[v] for _, v in group)
        if stake >= quorum:
            break
    return delay


def schedule(offsets_ns: list[int], groups: list[tuple[float, list]]) -> list[tuple[int, int, int]]:
    """The injector's deliveries, merged: (ns after the schedule's start,
    tx, frame), in the order they fall due."""
    delays_ns = [int(delay_ms * 1e6) for delay_ms, _ in groups]
    return sorted(
        (offset + delay, i, g)
        for i, offset in enumerate(offsets_ns) for g, delay in enumerate(delays_ns)
    )


class PeerInjector(threading.Thread):
    """Plays the peers: frame g of tx i reaches the vote pool at its due
    time, from the relaying peer 1 + g."""

    def __init__(self, sut, corp, first_tx: int, offsets_ns: list[int],
                 groups: list[tuple[float, list]]):
        super().__init__(name="peer-injector", daemon=True)
        self._sut, self._corp, self._first, self._t0 = sut, corp, first_tx, 0
        self._signers = [signers for _, signers in groups]
        self._schedule = schedule(offsets_ns, groups)
        self.late_ns: list[int] = []
        self.error: BaseException | None = None

    def begin(self, t0_ns: int) -> None:
        """Start delivering, the schedule's start at t0_ns (monotonic)."""
        self._t0 = t0_ns
        self.start()

    def run(self) -> None:
        try:
            for at, i, g in self._schedule:
                due = self._t0 + at
                client.sleep_until(due)
                self.late_ns.append(time.monotonic_ns() - due)
                self._sut.deliver_tx_votes(
                    self._corp, self._first + i, self._signers[g], sender=1 + g
                )
        except BaseException as e:
            self.error = e
