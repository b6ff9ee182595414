"""Route, tally and commit: median of the ``route`` stage spans, one a step,
``_route_result`` whole."""
import statistics


def read(ctx):
    spans = ctx["spans"]("route", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
