"""Mean host time to answer one flush's tickets over the window (the
``resolve_ms`` stage: the ticket loop with the answer frames its
callbacks encode and write)."""
from readings import stage_mean_ms


def read(ctx):
    return stage_mean_ms(ctx, "resolve_ms")
