"""Roofline share of the pool's stream-step program (``_pool_step``): the
least time for the stream steps answered while the trace ran, over the
program's device time in the trace."""
from readings import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "_pool_step")
