"""The expert layers' routed path outside its grouped matmuls, in device time.

Milliseconds a step a chip: the self time of the traced window's device ops
under the model scopes ``moe_route`` (the router's matmul, scores, top-k, gates),
``moe_dispatch`` (the sort of the assignments, the gather of their rows) and
``moe_combine`` (rows back to their tokens, weighted by the gates);
``moe_experts`` (the grouped matmuls: ``moe_experts_roofline``) and
``moe_shared`` are not in it
(``perf/hlo_scopes.py``: the LAST model scope in the op's ``op_name`` path;
forward and backward together, the trunk's blocks and the multi-token-
prediction block's), over the runs of the step's program on the ``XLA
Modules`` line. A program without the scope, or a driver that hands out no
compiled text, gives nothing to read.
"""

_SCOPES = ('moe_route', 'moe_dispatch', 'moe_combine')


def read(ctx):
    from perf import trace_reduce

    return trace_reduce.per_step_ms(ctx.reduction, "scope_seconds", _SCOPES)
