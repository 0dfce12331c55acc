"""The forward pass's device time in a training step.

Milliseconds a step a chip: the self time of the traced window's device ops
whose instruction the compiled step's text places under the phase
``forward_backward`` with no ``transpose(`` in the path: loss scaling and the
model's forward (what the backward pass recomputes is not among them: JAX
marks that ``transpose`` too)
(``perf/hlo_scopes.py``: by the op's own ``op_name`` path, else its fusion's
majority, its caller's, its nearest user's), over the runs of the step's
program on the ``XLA Modules`` line. A driver that hands out no compiled text
gives nothing to read.
"""

_PARTS = ('forward',)


def read(ctx):
    from perf import trace_reduce

    return trace_reduce.per_step_ms(ctx.reduction, "phase_seconds", _PARTS)
