"""Mean time a window waited in the micro-batcher before its flush (the
``queue_wait_ms`` stage)."""
from readings import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "queue_wait_ms")
