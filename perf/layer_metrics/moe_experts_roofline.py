"""The routed experts' grouped matmuls' share of their roofline in a
JoyAI-LLM-Flash training step.

Device time: the ``XLA Ops`` events whose HLO instruction is named
``gmm.<n>`` or ``tgmm.<n>`` (the grouped-matmul kernels ``transformer/
moe.py`` runs the experts with name themselves so) and whose
``custom_call_target`` is ``tpu_custom_call``: the grouped matmuls,
forward (twice: the routed part is recomputed in the backward pass),
backward-to-rows and backward-to-weights, of every expert layer.

Least time: the operations and bytes the COUNTED rows need
(``perf/joyai_flops.py:experts_train_cost``: 6 operations a parameter a row
that landed on a held expert, from the program's ``moe_rows_here`` counter;
the held experts' weights and the rows' operands across HBM), whatever
implements them, against the chip's peaks. Steps in the trace come from the
flash kernels' calls (three a block a step). A program without the kernels
or the counter (the parent) gives nothing to read.
"""

import re

_NAME = re.compile(r"^t?gmm(\.\d+)*$")
_TARGET = "tpu_custom_call"


def read(ctx):
    r = ctx.reduction
    rows = ctx.counters.get("moe_rows_here_per_step")
    if not r or ctx.peaks is None or not rows:
        return None
    secs = sum(s for name, s in r["op_seconds"].items()
               if _NAME.match(name) and r["op_stats"].get(name, {}).get(
                   "custom_call_target") == _TARGET)
    from perf import flops, joyai_flops
    from perf.layer_metrics import mla_attn_roofline as mla

    _, flash = mla.flash_calls(r)
    if secs <= 0 or flash == 0:
        return None

    cfg = ctx.config
    steps = flash / float(3 * mla.blocks_of(cfg) * r["chips"])
    expert_layers = mla.blocks_of(cfg) - cfg["first_k_dense_replace"]
    ops, nbytes = joyai_flops.experts_train_cost(cfg, rows, expert_layers)
    least, _bound = flops.roofline_seconds(ops, nbytes, ctx.peaks)
    return 100.0 * least * steps / secs
