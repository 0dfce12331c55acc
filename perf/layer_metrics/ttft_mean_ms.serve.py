"""Mean, over ALL requests due in the window, of DUE time to first token
visible: the wait for the tick under way (and for a lane, when all are busy),
the request's prefill and the decode step the same tick runs (host clock,
from outside the engine: a token counts when the ``tick`` that produced it
has returned). What a chat user waits before anything appears, and the
steadiest statistic of it there is: 2.3% and 5.6% between quartiles in two
sets of six runs (the wait is spread over a whole tick, and 52 requests a
window average it to 4 ms of 195), too wide for a bound of 0.1 to be safe, so
it is per-layer and nothing judged moves with it yet (PERF.md 2)."""


def read(ctx):
    return ctx.counters.get("ttft_mean_ms") or None
