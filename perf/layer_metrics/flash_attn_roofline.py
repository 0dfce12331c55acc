"""Flash-attention kernels' share of their roofline in a GPT training step.

Device time: the ``XLA Ops`` events whose ``kernel_metadata`` names a kernel
``flash_...`` (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``: the
forward, dq and dk/dv kernels of ``apex_tpu/ops/attention.py``; the name the
program gave the kernel, whatever XLA calls the instruction), each over the
whole global batch. Steps in the trace: the runs of the step's program on the ``XLA
Modules`` line.

Least time: the operations and bytes attention needs for those calls
(``perf/flops.py:attention_train_cost``: six matmuls of 2*s*s*d a head, halved
under the causal mask, the backward's recomputed scores not counted; twelve
(b, h, s, d) bf16 tensors across HBM), against the chip's peaks; at these
shapes the compute bound is the larger. The share is least time / device
time over however many steps the trace holds.
"""


def read(ctx):
    if "n_head" not in ctx.config:
        return None
    from perf import flops

    cfg, cell = ctx.config, ctx.cell
    layers, heads = cfg["n_layer"], cfg["n_head"]
    ops, nbytes = flops.attention_train_cost(
        cell["global_batch"], heads, cell["seq_len"],
        cfg["n_embd"] // heads, layers)
    return flops.roofline_share(ctx.reduction, flops.FLASH_KERNELS, ops,
                                nbytes, ctx.peaks)
