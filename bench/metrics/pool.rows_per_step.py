"""Streams advanced per pool step over the window: the growth of the
``pool.stream_steps`` counter over that of ``pool.steps``."""
from readings import counter_delta


def read(ctx):
    steps = counter_delta(ctx, "pool.steps")
    if steps <= 0:
        return None
    return counter_delta(ctx, "pool.stream_steps") / steps
