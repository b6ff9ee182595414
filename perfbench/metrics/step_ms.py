"""H2D + fused step + readback: median device time of one execution of
the step program over the traced steps (line "XLA Modules" of the device).
The median, since a light cell's traced steps may hold one execution of a
wider program after a pause."""
import statistics


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["steps"]:
        return None
    return 1e3 * statistics.median(trace["steps"])
