"""Coalescer: median hold of a partial batch before its linger or idle
flush, from the program's ``linger_bulk`` spans (one per flush)."""
import statistics


def read(ctx):
    spans = ctx["spans"]("linger_bulk", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
