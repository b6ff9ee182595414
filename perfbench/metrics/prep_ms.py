"""Host prep: median of the ``host_prep`` stage spans, one a step: drain,
dedup, slots, prior stake and sign bytes of the step's batch."""
import statistics


def read(ctx):
    spans = ctx["spans"]("host_prep", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
