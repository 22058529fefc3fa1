"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window), averaged over chips."""
from readings import idle_pct


def read(ctx):
    return idle_pct(ctx)
