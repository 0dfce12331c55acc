"""Latent attention's flash kernels' share of their roofline in a
JoyAI-LLM-Flash training step.

Device time: the ``XLA Ops`` events whose ``kernel_metadata`` names a kernel
``flash_...`` (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``): the
forward, dq and dk/dv kernels of ``apex_tpu/ops/attention.py`` at q/k 192 wide and v 128,
three calls a block a step (the trunk's blocks and the multi-token-
prediction block's), each over the whole batch. Steps in the trace: the
runs of the step's program on the ``XLA Modules`` line.

Least time: the operations and bytes those calls need
(``perf/joyai_flops.py:attention_train_cost``) against the chip's peaks. A
program without these kernels gives nothing to read.
"""


def read(ctx):
    if "layers_kept" not in ctx.config:
        return None
    from perf import flops, joyai_flops

    cfg, cell = ctx.config, ctx.cell
    blocks = cfg["layers_kept"] + cfg["num_nextn_predict_layers"]
    ops, nbytes = joyai_flops.attention_train_cost(
        cell["global_batch"], cell["seq_len"], blocks, cfg)
    return flops.roofline_share(ctx.reduction, flops.FLASH_KERNELS, ops,
                                nbytes, ctx.peaks)
