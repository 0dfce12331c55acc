"""The share of expert-layer calls a step that took the short sorted buffer
(sized by the share of experts held) and not the worst-case fallback,
averaged over the window's steps: the program's own counter
(``moe_compact_share`` in the step's MetricBag, which the driver hands on).
1 means no call fell back. A program without the counter gives nothing to
read."""


def read(ctx):
    return ctx.counters.get("moe_compact_share") or None
