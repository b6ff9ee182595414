"""Route / tally / commit: ``pipeline_stats()["route_s"]`` spent in the
window over the votes routed in it."""


def read(ctx):
    if ctx["votes"] <= 0:
        return None
    return 1e6 * ctx["pipeline"]["route_s"] / ctx["votes"]
