"""Route, tally and commit: certificate rows a commit decided in the
window, ``pipeline_stats()`` ``quorum_rows`` over ``quorums`` (both counted
where routing decides a quorum), each as the difference between the
window's close and its opening. Equal stake: 3 of 4, 43 of 64; a long-tailed
stake: where the frames' stake first passes 2/3. A program without the
counters (before PR 36) gives nothing to read."""


def read(ctx):
    opened, closed = ctx["counters"]["open"]["pipeline"], ctx["counters"]["close"]["pipeline"]
    if "quorum_rows" not in closed or "quorum_rows" not in opened:
        return None
    quorums = closed["quorums"] - opened["quorums"]
    if quorums <= 0:
        return None
    return (closed["quorum_rows"] - opened["quorum_rows"]) / quorums
