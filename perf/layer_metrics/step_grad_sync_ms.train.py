"""The data-parallel gradient exchange's device time in a training step.

Milliseconds a step a chip: the self time of the traced window's device ops
whose instruction the compiled step's text places under the phase
``grad_sync``: DDP's all-reduce of the fp32 gradients and the ``psum``s
beside it, whether or not compute overlaps them
(``perf/hlo_scopes.py``: by the op's own ``op_name`` path, else its fusion's
majority, its caller's, its nearest user's), over the runs of the step's
program on the ``XLA Modules`` line. A driver that hands out no compiled text
gives nothing to read.
"""

_PARTS = ('grad_sync',)


def read(ctx):
    from perf import trace_reduce

    return trace_reduce.per_step_ms(ctx.reduction, "phase_seconds", _PARTS)
