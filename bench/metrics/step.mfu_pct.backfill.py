"""Whole-step model FLOPs utilization: useful model FLOPs answered in the
window over window x chips x the chip's peak."""
from readings import mfu_pct


def read(ctx):
    return mfu_pct(ctx)
