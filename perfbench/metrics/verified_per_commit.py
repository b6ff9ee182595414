"""Route, tally and commit: votes the engine routed in the window (every
one verified on the device first) over the txs whose commit event fell in
it. At 64 validators of equal stake 43 is the quorum, 48 what drains of 16
validators' votes allow (the quorum latches in the third), and 64 says
that the votes which arrive after the commit are verified all the same."""


def read(ctx):
    commits = sum(1 for t in ctx["commit_times"] if ctx["t_open"] <= t < ctx["t_close"])
    if commits <= 0 or ctx["votes"] <= 0:
        return None
    return ctx["votes"] / commits
