"""Engine loop: share of the window spent in collections of Python's
collector, from the program's ``gc_pause`` spans (every generation)."""


def read(ctx):
    spans = ctx["spans"]("gc_pause", ctx["t_open"], ctx["t_close"])
    if not spans or ctx["window_s"] <= 0:
        return None
    return 100.0 * sum(spans) / ctx["window_s"]
