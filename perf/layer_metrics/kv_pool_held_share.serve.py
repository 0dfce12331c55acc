"""Share of the KV pool's token slots that hold a live token, averaged over
the window's ticks: every lane's prompt and the tokens served to it so far,
counted by the driver from the requests it watches, over ``num_blocks x
block_size``. Not reservation (``kv_pool_peak_share.serve``): what the
traffic really keeps in the memory the cell reserves."""


def read(ctx):
    held = ctx.counters.get("kv_held_mean_tokens")
    if not held or not ctx.counters.get("kv_pool_tokens"):
        return None
    return held / ctx.counters["kv_pool_tokens"]
