"""Commit: median of the ``commit_apply`` spans, from a tx's quorum decision
to its commit applied (TxStore, ABCI, commitpool, event queued) on whichever
thread applies it."""
import statistics


def read(ctx):
    spans = ctx["spans"]("commit_apply", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
