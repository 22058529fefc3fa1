"""Mean host time to launch one pool step over the window (the
``pool_launch_ms`` stage: the step program's and the error read's
dispatch, until they return)."""
from readings import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "pool_launch_ms")
