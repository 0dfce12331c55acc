"""Device-time timeline: measured profiler seconds joined to predicted bytes.

The missing consumer of the traces ``ProfilerTrigger`` and
``utils.trace`` write: a pure-Python analyzer over the trace-event JSON
(``*.trace.json.gz`` under the TensorBoard ``plugins/profile`` layout)
that answers, per training step, where the wall clock went —

- ``parser``   — the one blessed reader of the trace-event format
  (``lint.trace-file`` pins that): complete events, lane labels,
  ``StepTraceAnnotation`` step spans, XLA op executions — on the CPU
  layout (``args.hlo_op``) and on a TPU's, whose event names are the
  instructions' whole HLO texts;
- ``hlo_scopes`` — ``scope_map``: the compiled step's text read into
  instruction name ->
  (step phase, forward/backward, flax module, Pallas kernel): the
  program's own names (``goodput.scopes``) for XLA's;
- ``analyzer`` — step segmentation, compute/collective/memcpy/idle
  partition (union math over overlapping lanes, async
  ``-start``/``-done`` pairs fused), exposed-comms time, overlap and
  bubble fractions, and the bandwidth join: measured per-axis
  collective seconds (events attributed through the parsed HLO module's
  ``replica_groups``) against the xray ledger's predicted per-axis
  bytes -> achieved bytes/s vs the ICI roofline; and the scope join:
  device self time by phase, kernel and module, idle gaps by the host
  annotation (a goodput span) that covers them.

CLI: ``python -m apex_tpu.monitor.xray.timeline <logdir> [--hlo
<compiled step text>]``; the examples' ``--profile-analyze`` runs the
same path on the capture they just took and writes the compiled text
beside it. Records emit as ``kind="profile"`` through the MetricRouter
schema. See docs/observability.md#timeline.
"""

from apex_tpu.monitor.xray.timeline.parser import (
    StepSpan,
    Timeline,
    TraceEvent,
    find_trace_files,
    load_trace_json,
    parse_logdir,
    parse_trace,
    parse_trace_file,
)
from apex_tpu.monitor.xray.timeline.analyzer import (
    AxisBandwidth,
    ScopeBreakdown,
    StepBreakdown,
    TimelineReport,
    analyze,
    analyze_logdir,
    attribute_scopes,
    classify_op,
    pair_async_collectives,
    self_times,
)
from apex_tpu.monitor.xray.timeline.hlo_scopes import (
    OpScope,
    classify_path,
    scope_map,
)

__all__ = [
    "TraceEvent",
    "StepSpan",
    "Timeline",
    "find_trace_files",
    "load_trace_json",
    "parse_trace",
    "parse_trace_file",
    "parse_logdir",
    "classify_op",
    "pair_async_collectives",
    "StepBreakdown",
    "AxisBandwidth",
    "ScopeBreakdown",
    "self_times",
    "attribute_scopes",
    "OpScope",
    "classify_path",
    "scope_map",
    "TimelineReport",
    "analyze",
    "analyze_logdir",
]
