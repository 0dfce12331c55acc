"""The routed experts' grouped matmuls' share of their roofline in a
JoyAI-LLM-Flash training step.

Device time: the Mosaic custom-calls (``custom_call_target``
``tpu_custom_call``) whose HLO instruction is named ``gmm.<n>`` or
``tgmm.<n>`` (the library's grouped-matmul kernels name themselves so and
carry no ``kernel_metadata``): the grouped matmuls, forward (twice: the
routed part is recomputed in the backward pass), backward-to-rows and
backward-to-weights, of every expert layer.

Least time: the operations and bytes the COUNTED rows need
(``perf/joyai_flops.py:experts_train_cost``: 6 operations a parameter a row
that landed on a held expert, from the program's ``moe_rows_here`` counter;
the held experts' weights and the rows' operands across HBM), whatever
implements them, against the chip's peaks. Steps in the trace: the runs of
the step's program on the ``XLA Modules`` line. A program without the
kernels or the counter gives nothing to read.
"""

_KERNELS = "*gmm"  # gmm and tgmm


def read(ctx):
    rows = ctx.counters.get("moe_rows_here_per_step")
    if not rows or "layers_kept" not in ctx.config:
        return None
    from perf import flops, joyai_flops

    cfg = ctx.config
    expert_layers = (cfg["layers_kept"] + cfg["num_nextn_predict_layers"]
                     - cfg["first_k_dense_replace"])
    ops, nbytes = joyai_flops.experts_train_cost(cfg, rows, expert_layers)
    return flops.roofline_share(ctx.reduction, _KERNELS, ops, nbytes,
                                ctx.peaks)
