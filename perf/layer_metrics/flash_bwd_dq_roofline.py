"""The flash-attention backward-to-q kernel's share of its roofline in a training
step.

Device time: the ``XLA Ops`` events whose ``kernel_metadata`` names
``flash_bwd_dq`` (the name ``apex_tpu/ops/attention.py`` gave the kernel, whatever
XLA calls the instruction). Steps in the trace: the runs of the step's
program on the ``XLA Modules`` line.

Least time: the two matmuls and the tensors this kernel's job needs
(``perf/flops.py:flash_kernel_costs``: a third of attention's six matmuls,
what a backward kernel recomputes not counted) for the cell's shape
(``attention_shape``: GPT-2's heads or latent attention's 192 / 128), against
the chip's peaks, whichever of the two bounds is the larger.
"""

_KERNEL = "flash_bwd_dq"


def read(ctx):
    from perf import flops

    shape = flops.attention_shape(ctx.config, ctx.cell)
    if shape is None:
        return None
    ops, nbytes = flops.flash_kernel_costs(*shape)[_KERNEL]
    return flops.roofline_share(ctx.reduction, _KERNEL, ops, nbytes,
                                ctx.peaks)
