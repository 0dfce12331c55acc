"""What watches a training step, in device time.

Milliseconds a step a chip: the self time of the traced window's device ops
whose instruction the compiled step's text places under the phases
``unscale`` (the scaler's unscale with its overflow check over every
gradient, and its update) and ``guard`` (the sentinel's gate, the MetricBag
taps with the gradient norm, the per-layer RMS)
(``perf/hlo_scopes.py``: by the op's own ``op_name`` path, else its fusion's
majority, its caller's, its nearest user's), over the runs of the step's
program on the ``XLA Modules`` line. A driver that hands out no compiled text
gives nothing to read.
"""

_PARTS = ('unscale', 'guard')


def read(ctx):
    from perf import trace_reduce

    return trace_reduce.per_step_ms(ctx.reduction, "phase_seconds", _PARTS)
