"""The time work waited for the device: median of the ``collect_wait``
stage spans, the engine blocked in ``ticket.result()``."""
import statistics


def read(ctx):
    spans = ctx["spans"]("collect_wait", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
