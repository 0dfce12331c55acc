"""How late the load generator ran: 95th percentile of submission time less
due time. The driver submits between ticks, on the engine's own thread (as
``serve_gpt.main`` does), so a request waits for the tick under way; the
latencies count that wait, and this says how much of them it is."""


def read(ctx):
    return ctx.counters.get("loadgen_late_p95_ms") or None
