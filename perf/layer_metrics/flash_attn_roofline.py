"""Flash-attention kernels' share of their roofline in a GPT training step.

Device time: the ``XLA Ops`` events whose HLO instruction is named
``self_attention.<n>`` (the flax module's scope, which XLA keeps in the
instruction's name) and whose ``custom_call_target`` is ``tpu_custom_call``
(a Mosaic kernel): the forward, dq and dk/dv kernels of
``apex_tpu/ops/attention.py``, three calls a layer a step, each over the whole
global batch. That is what a chip trace carries today; the ``pallas_call``s
themselves have no ``name=``.

Least time: the operations and bytes attention needs for those calls
(``perf/flops.py:attention_train_cost``: six matmuls of 2*s*s*d a head, halved
under the causal mask, the backward's recomputed scores not counted; twelve
(b, h, s, d) bf16 tensors across HBM), against the chip's peaks; at these
shapes the compute bound is the larger. The share is least time / device
time over however many steps the trace holds.
"""

import re

_NAME = re.compile(r"^self_attention(\.\d+)*$")
_TARGET = "tpu_custom_call"
_CALLS_PER_LAYER = 3  # forward, dq, dk/dv


def read(ctx):
    r = ctx.reduction
    if not r or ctx.peaks is None:
        return None
    secs, calls = 0.0, 0
    for name, s in r["op_seconds"].items():
        target = r["op_stats"].get(name, {}).get("custom_call_target")
        if _NAME.match(name) and target == _TARGET:
            secs += s
            calls += r["op_counts"][name]
    if calls == 0 or secs <= 0:
        return None
    from perf import flops

    cfg, cell = ctx.config, ctx.cell
    layers, heads = cfg["n_layer"], cfg["n_head"]
    steps = calls / float(_CALLS_PER_LAYER * layers * r["chips"])
    ops, nbytes = flops.attention_train_cost(
        cell["global_batch"], heads, cell["seq_len"],
        cfg["n_embd"] // heads, layers)
    least, _bound = flops.roofline_seconds(ops, nbytes, ctx.peaks)
    return 100.0 * least * steps / secs
