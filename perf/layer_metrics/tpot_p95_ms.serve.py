"""95th percentile over all gaps between consecutive output tokens of the
requests due in the window that finished (host clock, from outside the
engine, as ``tpot_p50_ms``; the pair a prefill tick makes visible together
left out). One tick in ten also runs a prompt's prefill, so this tail lies
among those ticks: what a prompt costs the tokens of everyone else. Per-layer
and not end to end: a process runs its whole life in one of the host's two
speeds (PERF.md 6), which lie 4.5% apart here and 3.1% apart at the
median."""


def read(ctx):
    return ctx.counters.get("tpot_p95_ms") or None
