"""Share of the rows the score program computed that were timesteps of a
real window, over the window's flushes: growth of ``batch.rows_useful``
(true window lengths) over that of ``batch.rows_computed`` (lanes x
bucket length)."""
from readings import counter_delta


def read(ctx):
    computed = counter_delta(ctx, "batch.rows_computed")
    if computed <= 0:
        return None
    return 100.0 * counter_delta(ctx, "batch.rows_useful") / computed
