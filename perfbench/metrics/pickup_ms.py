"""Engine loop: median of the ``pickup_wait`` stage spans, from the first
vote the pool accepted since the engine's last drain to the engine taking
the batch up (its lane's hold begins, or its ``host_prep``): the thread hop
from the inserting thread and, with a step in flight, the rest of that
step's ``dispatch``."""
import statistics


def read(ctx):
    spans = ctx["spans"]("pickup_wait", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
