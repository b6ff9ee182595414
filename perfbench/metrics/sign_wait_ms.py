"""Sign walk: median of the ``sign_wait`` spans, from the mempool insert to
the moment the sign walk takes the tx up (a thread hop)."""
import statistics


def read(ctx):
    spans = ctx["spans"]("sign_wait", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
