"""Engine loop and coalescer, per tx: median of the ``vote_wait`` spans,
from a tx's first vote in the pool to the ``host_prep`` of the step that
drains it: what ``pickup_ms`` and ``linger_ms`` read a step, as the tx
met it (the second tx of a two-tx step waited less than the step's hold)."""
import statistics


def read(ctx):
    spans = ctx["spans"]("vote_wait", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
