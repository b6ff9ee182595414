"""Host prep: median of the ``carry_prior`` stage spans, one a step: the
loop that reads each slot's stake out of the open vote sets into the
step's prior array."""
import statistics


def read(ctx):
    spans = ctx["spans"]("carry_prior", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
