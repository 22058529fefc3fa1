"""Share of the KV room the pool's attention reads that holds real tokens,
over the window: growth of ``pool.kv_tokens`` (the cache length of each
row a step carries, summed) over that of ``pool.kv_room`` (those rows x
``max_seq_len``).  Only a model with a KV cache counts them."""
from readings import counter_delta


def read(ctx):
    room = counter_delta(ctx, "pool.kv_room")
    if room <= 0:
        return None
    return 100.0 * counter_delta(ctx, "pool.kv_tokens") / room
