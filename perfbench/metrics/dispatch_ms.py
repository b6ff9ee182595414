"""H2D and launch: median of the ``dispatch`` stage spans, one a step: pad,
``device_put`` and enqueue, until ``submit`` returns."""
import statistics


def read(ctx):
    spans = ctx["spans"]("dispatch", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
