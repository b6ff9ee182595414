"""Route and tally: seconds of the ``route_tally`` stage spans in the window
(under the engine's lock: routing, quorum decisions, removal of votes that
can never be added) over the votes routed in it."""


def read(ctx):
    spans = ctx["spans"]("route_tally", ctx["t_open"], ctx["t_close"])
    if not spans or ctx["votes"] <= 0:
        return None
    return 1e6 * sum(spans) / ctx["votes"]
