"""How far the busiest worker of a front ran ahead of the mean: with
``s_i`` the STEP answers worker ``i`` gave in the window (the growth of
its ``pool.stream_steps`` counter), ``100 * (max s_i - mean s) / mean s``."""
from readings import worker_counter_deltas


def read(ctx):
    steps = worker_counter_deltas(ctx, "pool.stream_steps")
    if len(steps) < 2 or sum(steps) <= 0:
        return None
    mean = sum(steps) / len(steps)
    return 100.0 * (max(steps) - mean) / mean
