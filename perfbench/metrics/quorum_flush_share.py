"""Coalescer: the share of the window's quorums whose hold ended because the
held votes completed a tx's quorum by stake, ``pipeline_stats()``
``coalesce.quorum_flushes`` over ``quorums``, each as the difference between
the window's close and its opening, in percent. Near 100 where each tx's
deciding frame is flushed as it lands; less where one flush carries several
quorums or a hold ends on its clock. A program whose coalescer keeps no such
counter gives nothing to read."""


def read(ctx):
    opened, closed = ctx["counters"]["open"]["pipeline"], ctx["counters"]["close"]["pipeline"]
    if "quorum_flushes" not in closed.get("coalesce", {}) or "quorum_flushes" not in opened.get("coalesce", {}):
        return None
    quorums = closed["quorums"] - opened["quorums"]
    if quorums <= 0:
        return None
    flushes = closed["coalesce"]["quorum_flushes"] - opened["coalesce"]["quorum_flushes"]
    return 100.0 * flushes / quorums
