"""Whole training step's share of the chip's peak: the operations the forward
and backward passes need (``perf/flops.py``, counted by the driver from the
cell's shapes; causal attention at its half, nothing recomputed) for every
step of the window, over the whole window (less the stall of starting the
profiler, in a traced run), divided by the bf16 peak of the chips used."""


def read(ctx):
    flops = ctx.counters.get("model_flops")
    if ctx.peaks is None or not flops:
        return None
    return 100.0 * flops / ctx.counters["window_s"] / (
        ctx.peaks["bf16_flops_per_s"] * ctx.chips)
