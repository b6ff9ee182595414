"""Engine loop: median of the per-tx ``quorum_wait`` spans, from the start
of the ``pickup_wait`` of the step that completes the tx's quorum (the
pool's first vote since the previous drain) to the commit decision: the
node's own share of a quorum whose last frame arrives last, the frames'
delays before that step left out. A program without the span (before
PR 36) gives nothing to read."""
import statistics


def read(ctx):
    spans = ctx["spans"]("quorum_wait", ctx["t_open"], ctx["t_close"])
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
