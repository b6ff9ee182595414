import random

import pytest

from perfbench.harness.flood import BacklogFeeder


class FakeNode:
    """Commits what it was fed, some txs at a time."""

    def __init__(self):
        self.fed: list[tuple[int, int]] = []
        self.n_committed = 0

    def feed(self, lo, hi):
        self.fed.append((lo, hi))

    def committed(self):
        return self.n_committed


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_backlog_stays_between_dry_and_one_chunk_over(seed):
    node = FakeNode()
    backlog, chunk = 2048, 256
    feeder = BacklogFeeder(backlog, chunk, 10**6, node.feed, node.committed)
    rng = random.Random(seed)
    feeder.pump()
    assert feeder.outstanding() == backlog  # filled to the mark, chunk by chunk
    for _ in range(500):
        # the node commits up to one rung between two looks of the feeder
        node.n_committed += rng.randrange(0, min(1025, feeder.outstanding() + 1))
        feeder.pump()
        assert backlog <= feeder.outstanding() < backlog + chunk
    assert feeder.max_outstanding <= backlog + chunk
    assert feeder.min_outstanding_before_feed > 0  # never dry
    # chunks are contiguous and in order
    assert all(b[0] == a[1] for a, b in zip(node.fed, node.fed[1:]))
    assert all(hi - lo == chunk for lo, hi in node.fed)


def test_a_node_that_outruns_the_feeder_reads_dry():
    node = FakeNode()
    feeder = BacklogFeeder(512, 256, 10**6, node.feed, node.committed)
    feeder.pump()
    node.n_committed = feeder.fed  # everything committed before the next look
    feeder.pump()
    assert feeder.min_outstanding_before_feed == 0


def test_corpus_end_is_reported_not_overrun():
    node = FakeNode()
    feeder = BacklogFeeder(512, 256, 600, node.feed, node.committed)
    feeder.pump()
    assert feeder.fed == 512 and not feeder.exhausted
    node.n_committed = 512
    feeder.pump()
    assert feeder.exhausted and feeder.fed == 512  # 88 left: less than a chunk


def test_align_rounds_the_tail_up_to_whole_rungs():
    node = FakeNode()
    feeder = BacklogFeeder(1280, 256, 10**6, node.feed, node.committed)
    feeder.pump()
    assert feeder.fed == 1280
    feeder.align(1024)
    assert feeder.fed == 2048


def test_a_backlog_smaller_than_a_chunk_is_refused():
    with pytest.raises(ValueError):
        BacklogFeeder(100, 256, 1000, lambda lo, hi: None, lambda: 0)


def test_a_feeder_that_cannot_catch_up_still_stops_when_told():
    node = FakeNode()

    def feed(lo, hi):
        node.fed.append((lo, hi))
        node.n_committed = hi  # the node commits as fast as it is fed

    feeder = BacklogFeeder(512, 256, 10**9, feed, node.committed)
    calls = []

    def stop():
        calls.append(1)
        return len(calls) > 10

    assert feeder.pump(stop) == 10
