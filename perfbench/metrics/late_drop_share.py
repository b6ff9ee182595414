"""Host prep: share of the window spent in the ``late_drop`` stage spans,
the vote pool's removal of drained votes that can never be added (their tx
has committed, or their validator's vote is already held). Only a drain
that dropped something records one, so a window without any reads 0. A
program that records no ``carry_prior`` span (one a step, added with
``late_drop``) does not have the family at all: nothing to read."""


def read(ctx):
    t0, t1 = ctx["t_open"], ctx["t_close"]
    if not ctx["spans"]("carry_prior", t0, t1) or t1 <= t0:
        return None
    return 100.0 * sum(ctx["spans"]("late_drop", t0, t1)) / (t1 - t0)
