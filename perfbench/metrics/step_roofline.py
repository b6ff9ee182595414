"""Kernel: the least time the chip could take for the votes of one rung
(``harness/roofline.py``: integer multiply-adds of an ed25519 verification
against the int8 peak, bytes against HBM) over the step's mean device
time. Finds nothing where no step was traced; never returns 0."""
from perfbench.harness import roofline


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["steps"]:
        return None
    mean = sum(trace["steps"]) / len(trace["steps"])
    return roofline.roofline_share(
        ctx["rung_votes"], ctx["rung_slots"], mean, ctx["device_kind"]
    )
