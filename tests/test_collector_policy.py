"""The node's schedule for Python's collector (utils/collector.py, PR 31).

A vote flood promotes nearly every object it allocates and frees it by
reference count, so under CPython's stock rule (a full collection once
the promotions reach a quarter of what survived the last one) every
promoted object costs four objects walked. The policy starts a full
collection when the promotions can equal the tracked heap, with the
start-up heap frozen out of the walk. Held here on the CPU, with no
device: the count of full collections under a pool's steady churn
against the stock rule's for the same traffic, a planted cycle still
collected, ``gc`` left as found by nodes that start and stop, and the
four counters in ``pipeline_stats()`` and ``/health``.

The churn keeps 204,800 votes resident (409,600 tracked objects: a
resident vote is its ``TxVote`` and ONE record tuple since PR 34, where
it was three objects): in a smaller heap the stock rule's other
condition, ten middle collections, binds long before the quarter does,
and a run would count that. A frame is 1,024 votes: the young
generation's count falls with every object freed by reference count, so
a churn in steps of under 700 objects never starts a collection of any
generation.
"""

import conftest  # noqa: F401

import gc
import hashlib
import weakref

import pytest

from txflow_tpu.node import LocalNet
from txflow_tpu.pool.txvotepool import TxVotePool, vote_key
from txflow_tpu.types import TxVote
from txflow_tpu.utils.collector import COLLECTOR, CollectorPolicy
from txflow_tpu.utils.config import MempoolConfig

FRAME = 1024
RESIDENT = 200 * FRAME
CHURN = 4_000 * FRAME  # the most votes a phase puts through the pool


class _FullCollections:
    """Counts the full collections between enter and exit."""

    def __init__(self):
        self.n = 0

    def __call__(self, phase, info):
        if phase == "stop" and info["generation"] == 2:
            self.n += 1

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *_exc):
        gc.callbacks.remove(self)


def _as_found():
    """What a process can find again: a full collection moves the
    interpreter's immortal objects back into the permanent generation
    (375 of them here), which ``gc.unfreeze()`` had emptied."""
    gc.collect()
    return gc.get_threshold(), gc.get_freeze_count()


@pytest.fixture(scope="module")
def votes():
    """RESIDENT + 2 frames of distinct votes with their keys; a vote is
    ingested again only after its removal, dedup entry included."""
    out = []
    for i in range(RESIDENT + 2 * FRAME):
        k = hashlib.sha256(b"collector-%d" % i).digest()
        v = TxVote(1, k.hex().upper(), k, 1_700_000_000_000_000_000 + i, k[:20], k + k)
        out.append((vote_key(v), v))
    return out


class _Churn:
    """A TxVotePool held at RESIDENT votes: a frame in through
    ``check_tx_many``, the oldest frame out through ``remove``. Every
    ingest allocates the vote's record, one tuple, which lives through
    hundreds of young collections and dies by reference count."""

    def __init__(self, votes):
        self.votes = votes
        self.pool = TxVotePool(
            MempoolConfig(size=2 * len(votes), cache_size=2 * len(votes))
        )
        self.head = 0  # next vote in
        self.tail = 0  # next vote out
        self.step(RESIDENT)

    def step(self, n_votes: int, until=None) -> int:
        """n_votes through the pool, or fewer once ``until()`` holds;
        returns how many went through."""
        ring, pool = self.votes, self.pool
        for i in range(n_votes // FRAME):
            if until is not None and until():
                return i * FRAME
            frame = ring[self.head : self.head + FRAME]
            self.head = (self.head + FRAME) % len(ring)
            refused = [r for r in pool.check_tx_many([v for _, v in frame]) if r]
            assert not refused, refused[:3]
            if pool.size() > RESIDENT:
                out = ring[self.tail : self.tail + FRAME]
                self.tail = (self.tail + FRAME) % len(ring)
                pool.remove([k for k, _ in out], cache_too=True)
        return n_votes // FRAME * FRAME


@pytest.fixture
def policy():
    """A policy of the test's own, removed whatever the test does."""
    p = CollectorPolicy()
    p.install()
    try:
        yield p
    finally:
        p.remove()
        p.remove()  # one too many is a no-op


def test_a_third_of_the_stock_rules_full_collections_under_pool_churn(votes):
    found = _as_found()
    churn = _Churn(votes)
    gc.collect()
    # the traffic is what gives the stock rule eight full collections: in
    # a process of 400,000 tracked objects it was 88 middle collections of
    # 11 frames (a full one every 11th: a quarter of the heap is 9 of
    # them) where this one runs a full one every 52nd (400,000 over the
    # 7,711 a middle collection is taken for). A larger process (this
    # one is 500,000 since PR 34 doubled the votes) stretches both
    with _FullCollections() as stock:
        traffic = churn.step(CHURN, until=lambda: stock.n == 8)
    assert stock.n == 8, (stock.n, traffic)
    policy = CollectorPolicy()
    policy.install()
    try:
        with _FullCollections() as mine:
            assert churn.step(traffic) == traffic
        stats = policy.stats()
    finally:
        policy.remove()
    assert 1 <= mine.n and 3 * mine.n <= stock.n, (mine.n, stock.n)
    assert stats["full_collections"] == mine.n and stats["full_collect_s"] > 0
    # a vote's record; the TxVotes are frozen
    assert stats["survivors"] >= RESIDENT
    assert stats["frozen_objects"] >= len(votes)
    del churn
    assert _as_found() == found


def test_threshold_follows_what_the_last_full_collection_left(policy):
    t0, t1, t2 = gc.get_threshold()
    per_middle = (t0 + 1) * (t1 + 1)  # what a middle collection is taken to promote
    frozen = policy.stats()["frozen_objects"]  # counted once, at the freeze
    # the start-up heap, less what reference counts have freed since
    assert frozen >= gc.get_freeze_count() > 10_000
    assert t2 == max(10, frozen // per_middle)
    kept = [[i] for i in range(40 * per_middle)]
    gc.collect()
    stats = policy.stats()
    assert stats["survivors"] >= len(kept) and stats["full_collections"] == 1
    assert gc.get_threshold() == (t0, t1, (stats["survivors"] + frozen) // per_middle)
    assert gc.get_threshold()[2] >= t2 + 40
    del kept
    gc.collect()  # the heap shrank: so does the interval, never under the floor found
    assert policy.stats()["survivors"] < 40 * per_middle
    assert 10 <= gc.get_threshold()[2] < t2 + 40


def test_a_planted_cycle_is_collected_within_two_doublings(votes, policy):
    churn = _Churn(votes)
    gc.collect()  # the schedule is now derived from the heap with the pool in it
    heap = policy.stats()["survivors"] + gc.get_freeze_count()

    class Node:
        pass

    a, b = Node(), Node()
    a.other, b.other = b, a
    gone = weakref.ref(a)
    gc.collect(1)  # into the oldest generation: no young collection finds it
    del a, b
    promoted = 0
    while gone() is not None and promoted <= 2 * heap:
        churn.step(10 * FRAME)
        promoted += 10 * FRAME  # a record an ingest
    assert gone() is None, (promoted, heap, gc.get_threshold())
    assert policy.stats()["full_collections"] == 2  # the one above, and the one that found it


def test_an_embedders_thresholds_are_kept_and_restored():
    found = _as_found()
    gc.set_threshold(500, 5, 20)
    try:
        policy = CollectorPolicy()
        policy.install()
        policy.install()  # a second node
        assert gc.callbacks.count(policy) == 1
        t0, t1, t2 = gc.get_threshold()
        assert (t0, t1) == (500, 5) and t2 >= 20  # the floor is the one found
        assert gc.get_freeze_count() > found[1]
        policy.remove()
        assert gc.callbacks.count(policy) == 1 and gc.get_freeze_count() > found[1]
        policy.remove()
        assert policy not in gc.callbacks
        assert gc.get_threshold() == (500, 5, 20)
    finally:
        gc.set_threshold(*found[0])
    assert _as_found() == found


def test_node_start_stop_twice_leaves_gc_as_found():
    found = _as_found()
    for _ in range(2):
        net = LocalNet(1, use_device_verifier=False)
        net.start()
        try:
            assert gc.callbacks.count(COLLECTOR) == 1
            assert gc.get_freeze_count() > found[1] + 10_000
            assert gc.get_threshold()[:2] == found[0][:2]
        finally:
            net.stop()
        assert COLLECTOR not in gc.callbacks
        assert _as_found() == found


def test_two_nodes_of_a_localnet_share_one_policy():
    found = _as_found()
    net = LocalNet(2, use_device_verifier=False)
    net.start()
    try:
        assert gc.callbacks.count(COLLECTOR) == 1
        net.nodes[0].stop()
        assert gc.callbacks.count(COLLECTOR) == 1  # the other node still runs
        assert gc.get_freeze_count() > found[1] + 10_000
    finally:
        net.stop()
    assert COLLECTOR not in gc.callbacks
    assert _as_found() == found


def test_pipeline_stats_and_health_carry_the_four_counters():
    net = LocalNet(1, use_device_verifier=False)
    node = net.nodes[0]
    names = ("full_collections", "full_collect_s", "survivors", "frozen_objects")
    net.start()
    try:
        before = node.txflow.pipeline_stats()
        assert before["full_collections"] == 0 and before["full_collect_s"] == 0
        # what start-up built (a frozen object is still freed by reference count)
        assert 10_000 < before["frozen_objects"] <= gc.get_freeze_count() + 1_000
        net.broadcast_tx(b"collector=v")
        assert net.wait_all_committed([b"collector=v"], timeout=60.0)
        gc.collect()  # an explicit collection fires the callbacks too
        stats = node.txflow.pipeline_stats()
        assert stats["full_collections"] == 1 and stats["full_collect_s"] > 0
        assert stats["survivors"] > 0 and 0 < stats["frozen_objects"] <= before["frozen_objects"]
        node.health.registry.refresh(node)
        pipeline = node.health.registry.snapshot()["progress"]["pipeline"]
        assert {n: pipeline[n] for n in names} == {n: stats[n] for n in names}
    finally:
        net.stop()
