"""Vote pool ingest: thread CPU the pool's ingest took in the window
(``TxVotePool.ingest_stats()`` ``cpu_s``: ``time.thread_time()`` read twice a
frame, so another thread's hold of the interpreter lock is not in it) over
the votes it ingested in it, in us a vote. Nothing where no vote came in."""


def read(ctx):
    opened, closed = ctx["counters"]["open"]["ingest"], ctx["counters"]["close"]["ingest"]
    votes = closed["votes"] - opened["votes"]
    if votes <= 0:
        return None
    return 1e6 * (closed["cpu_s"] - opened["cpu_s"]) / votes
