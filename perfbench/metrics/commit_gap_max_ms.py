"""Engine loop: the longest time between two commits inside the window."""
from perfbench.harness import stats


def read(ctx):
    if not ctx["commit_times"]:
        return None
    return 1e3 * stats.max_gap(ctx["commit_times"], ctx["t_open"], ctx["t_close"])
