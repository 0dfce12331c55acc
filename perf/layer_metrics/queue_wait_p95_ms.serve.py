"""95th percentile, over the requests due in the window, of the time from a
request's due time to the start of the tick that admitted it (host clock,
taken by the driver from outside the engine): waiting for a lane, for pool
blocks, or for the tick under way to end."""


def read(ctx):
    return ctx.counters.get("queue_wait_p95_ms") or None
