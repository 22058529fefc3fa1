"""Mean host wait for one pool step's errors over the window (the
``pool_readback_ms`` stage: from the launch's return until the values
are on the host)."""
from readings import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "pool_readback_ms")
