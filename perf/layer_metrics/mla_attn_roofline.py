"""Latent attention's flash kernels' share of their roofline in a
JoyAI-LLM-Flash training step.

Device time: the ``XLA Ops`` events whose HLO instruction is named
``self_attention.<n>`` and whose ``custom_call_target`` is
``tpu_custom_call``: the forward, dq and dk/dv kernels of
``apex_tpu/ops/attention.py`` at q/k 192 wide and v 128, three calls a block
a step (the trunk's blocks and the multi-token-prediction block's), each
over the whole batch.

Least time: the operations and bytes those calls need
(``perf/joyai_flops.py:attention_train_cost``) against the chip's peaks.
Steps in the trace: calls / (3 x blocks x chips). A program without these
kernels (the parent) gives nothing to read.
"""

import re

_NAME = re.compile(r"^self_attention(\.\d+)*$")
_TARGET = "tpu_custom_call"
_CALLS_PER_BLOCK = 3  # forward, dq, dk/dv


def flash_calls(r):
    """(seconds, calls) of the flash kernels in a reduction."""
    secs, calls = 0.0, 0
    for name, s in r["op_seconds"].items():
        target = r["op_stats"].get(name, {}).get("custom_call_target")
        if _NAME.match(name) and target == _TARGET:
            secs += s
            calls += r["op_counts"][name]
    return secs, calls


def blocks_of(cfg):
    return cfg["layers_kept"] + cfg["num_nextn_predict_layers"]


def read(ctx):
    r = ctx.reduction
    if not r or ctx.peaks is None or "layers_kept" not in ctx.config:
        return None
    secs, calls = flash_calls(r)
    if calls == 0 or secs <= 0:
        return None
    from perf import flops, joyai_flops

    cfg, cell = ctx.config, ctx.cell
    blocks = blocks_of(cfg)
    steps = calls / float(_CALLS_PER_BLOCK * blocks * r["chips"])
    ops, nbytes = joyai_flops.attention_train_cost(
        cell["global_batch"], cell["seq_len"], blocks, cfg)
    least, _bound = flops.roofline_seconds(ops, nbytes, ctx.peaks)
    return 100.0 * least * steps / secs
