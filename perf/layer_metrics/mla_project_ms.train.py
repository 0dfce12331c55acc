"""Latent attention outside its flash kernels, in device time.

Milliseconds a step a chip: the self time of the traced window's device ops
under the model scope ``mla_project``: the five projections, their two norms, the
rope
(``perf/hlo_scopes.py``: the LAST model scope in the op's ``op_name`` path;
forward and backward together, the trunk's blocks and the multi-token-
prediction block's), over the runs of the step's program on the ``XLA
Modules`` line. A program without the scope, or a driver that hands out no
compiled text, gives nothing to read.
"""

_SCOPES = ('mla_project',)


def read(ctx):
    from perf import trace_reduce

    return trace_reduce.per_step_ms(ctx.reduction, "scope_seconds", _SCOPES)
