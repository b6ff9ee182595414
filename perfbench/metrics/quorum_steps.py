"""Route, tally and commit: engine steps a quorum decided in the window
took, ``pipeline_stats()`` ``quorum_steps`` (for each committed tx the steps
that added a vote to its set, up to and including the deciding one) over
``quorums``, each as the difference between the window's close and its
opening. 1 where every vote of a tx rides one step; more where the votes
come in frames or in drains of their own. A program without the counters
(before PR 36) gives nothing to read."""


def read(ctx):
    opened, closed = ctx["counters"]["open"]["pipeline"], ctx["counters"]["close"]["pipeline"]
    if "quorum_steps" not in closed or "quorum_steps" not in opened:
        return None
    quorums = closed["quorums"] - opened["quorums"]
    if quorums <= 0:
        return None
    return (closed["quorum_steps"] - opened["quorum_steps"]) / quorums
