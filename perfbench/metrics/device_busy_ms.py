"""Fused step and readback as the host sees them: median of the
``device_busy`` stage spans, from a step's dispatch (or the step before it
being ready) to its packed result usable on the host, stamped by the
staging ring's thread once it holds the interpreter lock again. NOT the
device's time (that is ``step_ms``): its excess over ``step_ms`` is launch,
readback and that thread's wait for the lock, 20 ms a step in the flood."""
import statistics


def read(ctx):
    spans = ctx["spans"]("device_busy", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
