"""The most of the KV pool that was ever reserved: the engine's own counter
``stats()["kv_pool_peak_blocks"]`` over the pool's ``num_blocks``. The engine
reserves a request's prompt + ``max_new_tokens`` at admission, so this is
reservation, not live tokens."""


def read(ctx):
    peak = ctx.counters.get("kv_pool_peak_blocks")
    if not peak or not ctx.counters.get("kv_pool_blocks"):
        return None
    return peak / ctx.counters["kv_pool_blocks"]
