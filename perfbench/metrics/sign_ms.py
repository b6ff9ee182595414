"""Sign walk: median of the program's txtrace ``sign_walk`` spans that
began in the window (the traced run samples every tx)."""
import statistics


def read(ctx):
    spans = ctx["spans"]("sign_walk", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
