"""Event bus and websocket: median of the ``publish`` spans, from a commit's
event queued to its frame handed to a subscriber's socket: the event
worker's queue, the bus, the subscriber's queue and the pump."""
import statistics


def read(ctx):
    spans = ctx["spans"]("publish", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
