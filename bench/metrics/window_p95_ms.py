"""95th percentile of send-to-answer time over every SCORE frame sent
inside the window, on the load generator's clock."""
from readings import latency_p95_ms


def read(ctx):
    return latency_p95_ms(ctx, "score")
