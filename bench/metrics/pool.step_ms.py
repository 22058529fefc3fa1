"""Mean host-observed pool step over the window (the ``pool_step_ms``
stage: host assembly, the compiled step and the blocking readback)."""
from readings import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "pool_step_ms")
