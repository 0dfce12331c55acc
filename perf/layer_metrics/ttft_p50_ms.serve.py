"""Median, over the requests due in the window, of DUE time to first token
visible (host clock, from outside the engine, as ``ttft_mean_ms.serve``). A
request that waited for a lane moves the mean and not this; between runs it
is no steadier (3.5% and 5.5% between quartiles, PERF.md 2)."""


def read(ctx):
    return ctx.counters.get("ttft_p50_ms") or None
