"""RPC front door: median of the program's ``rpc_ingest`` spans, from a
``/broadcast_tx`` request parsed to the tx inserted in the mempool (the
admission verdict is inside it)."""
import statistics


def read(ctx):
    spans = ctx["spans"]("rpc_ingest", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
