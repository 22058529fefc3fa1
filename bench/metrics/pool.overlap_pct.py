"""Share of the window's pool steps that were chained: launched at the
previous step's completion, ahead of its answers, so that the session
pool's host work overlaps the device step (the growth of the
``pool.steps_chained`` counter over that of ``pool.steps``, in %).
Nothing where the program keeps no such counter."""
from readings import counter_delta


def read(ctx):
    if "pool.steps_chained" not in ctx["stats1"].get("counters", {}):
        return None
    steps = counter_delta(ctx, "pool.steps")
    if steps <= 0:
        return None
    return 100.0 * counter_delta(ctx, "pool.steps_chained") / steps
