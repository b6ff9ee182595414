"""Device: 1 minus the seconds in which an operation ran on the device
over the traced window, both on the trace's own clock: the window is whole
cycles, from the first traced program's start to the last one's."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
