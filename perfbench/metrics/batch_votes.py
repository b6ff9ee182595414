"""Coalescer: votes routed in the window over the engine's steps in it
(sum of the ``batch_size`` histogram and ``pipeline_stats()["steps"]``)."""


def read(ctx):
    steps = ctx["pipeline"]["steps"]
    if steps <= 0:
        return None
    return ctx["votes"] / steps
