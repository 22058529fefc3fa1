"""Stream samples answered inside the window (STEP answers, one per
sample), per second of it."""
from readings import answered_rate


def read(ctx):
    return answered_rate(ctx, "step")
