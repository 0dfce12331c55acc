"""95th percentile, over the requests due in the window, of due time to
first token (host clock, from outside the engine, as ``ttft_p50_ms.serve``).
Recorded, not judged: of the requests a window holds, two or three lie
beyond it."""


def read(ctx):
    return ctx.counters.get("ttft_p95_ms") or None
