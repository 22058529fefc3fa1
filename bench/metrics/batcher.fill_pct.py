"""Share of micro-batch lanes that carried a real window over the
window's flushes: growth of ``batch.filled`` over that of ``batch.slots``."""
from readings import counter_delta


def read(ctx):
    slots = counter_delta(ctx, "batch.slots")
    if slots <= 0:
        return None
    return 100.0 * counter_delta(ctx, "batch.filled") / slots
