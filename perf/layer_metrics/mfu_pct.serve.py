"""The whole serving path's share of the chip's peak: the operations the
tokens served inside the window need (``perf/serve_flops.py``, counted by the
driver: every finished prefill's prompt through the blocks with causal
attention and the head once, every decode step's token against its cache;
nothing a padded bucket, an idle lane or a gathered window computes besides)
over the whole window (less the stall of starting the profiler, in a traced
run), divided by the bf16 peak of the chips used. It bounds every kernel's
claim: a gain that takes a program off the path leaves this standing."""


def read(ctx):
    flops = ctx.counters.get("model_flops")
    if ctx.peaks is None or not flops:
        return None
    return 100.0 * flops / ctx.counters["window_s"] / (
        ctx.peaks["bf16_flops_per_s"] * ctx.chips)
