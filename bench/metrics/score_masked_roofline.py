"""Roofline share of the masked score program (``_score_masked``): the
least time for the windows answered while the trace ran, over the
program's device time in the trace."""
from readings import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "_score_masked")
