"""Stored windows scored inside the window (SCORE answers), per second
of it."""
from readings import answered_rate


def read(ctx):
    return answered_rate(ctx, "score")
