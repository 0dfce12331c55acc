"""Device-time breakdown + measured-vs-predicted bandwidth join.

The profiler traces ``ProfilerTrigger``/``utils.trace`` capture hold the
answer to "where did the step's wall clock GO?" — this module computes
it. Per step (segmented on the ``StepTraceAnnotation`` markers the
examples wrap each step in):

- **compute / collective / memcpy seconds** — union of the XLA op
  intervals of each class (never a sum: ops overlap across lanes, and
  an async collective's ``-start``/``-done`` pair is fused into ONE
  in-flight interval first);
- **exposed-comms seconds** — collective time NOT covered by compute:
  the part of the comms bill the schedule failed to hide (the quantity
  ROADMAP item 5's overlap schedules exist to drive to zero);
- **overlap fraction** — hidden / total collective time;
- **idle seconds and bubble fraction** — step span not covered by any
  device op: pipeline bubbles, host stalls, dispatch gaps.

The partition identity, pinned digit-for-digit in tests: ``compute +
exposed_collective + exposed_memcpy + idle == span``.

The bandwidth join closes the loop with PR 3's ledger: each measured
collective event is matched to its instruction in the compiled
``HloModule`` by NAME, its ``replica_groups`` (or permute pairs)
attributed to a mesh axis (``analysis/hlo/attribution.py``), and the
per-axis measured seconds divided into the ledger's predicted per-axis
wire bytes — **achieved bytes/s per mesh axis**, and with an ICI
bandwidth a measured utilization percentage. The static roofline table
becomes a measurement.

The scope join speaks the program's names: with the compiled step's
module (``module=``, the argument the bandwidth join already takes),
every device op event is looked up by instruction name in
``scope_map`` and its SELF time (its duration less the events nested in
it: a ``cond`` and the ops of its body overlap, and only the innermost
did the work) is booked to the step phase it was traced under
(``goodput.scopes.STEP_PHASES``, ``forward_backward`` split into forward
and backward), to the flax module, and — for a Pallas custom-call — to
the kernel its ``kernel_metadata`` names. The self times of one device
sum to its busy union, so the table's total is the device's busy time;
what no rule could place is reported as ``(unattributed)``, never
dropped. The device's idle time is put down, instant by instant, to
the innermost host annotation that covers it (the goodput spans open a
``jax.profiler.TraceAnnotation``, so ``data_wait``, ``ckpt_save``,
``snapshot`` are on the capture's clock; the serving engine's
``engine.<part>`` annotations nest inside its ``prefill`` / ``decode``
spans, and the innermost is the part of the tick the host was in).

Everything emits ``kind="profile"`` records through the shared
MetricRouter schema; ``python -m apex_tpu.monitor.xray.timeline`` is
the standalone entry point.

Caveat for CPU captures (the test topology): "device" ops run on the
XLA host threadpool, so compute/collective durations are real measured
seconds but the idle/bubble numbers include host scheduling noise, and
achieved "bandwidth" is memcpy rate, not ICI. The math is identical on
a real TPU capture; only the interpretation of absolute numbers changes
(docs/observability.md#timeline).
"""

import collections
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from apex_tpu.analysis.hlo.parser import COLLECTIVE_KINDS
from apex_tpu.monitor.goodput.scopes import KERNELS
from apex_tpu.monitor.goodput.spans import PHASES
from apex_tpu.monitor.xray.timeline.parser import (
    StepSpan,
    Timeline,
    TraceEvent,
    parse_logdir,
)
from apex_tpu.monitor.xray.timeline.hlo_scopes import (
    UNATTRIBUTED,
    OpScope,
    classify_path,
    scope_map,
    tiles_of,
)

__all__ = [
    "CLASS_COMPUTE",
    "CLASS_COLLECTIVE",
    "CLASS_MEMCPY",
    "classify_op",
    "op_base",
    "merge_intervals",
    "total_us",
    "intersect_intervals",
    "subtract_intervals",
    "clip_intervals",
    "pair_async_collectives",
    "OpInterval",
    "StepBreakdown",
    "AxisBandwidth",
    "ScopeBreakdown",
    "self_times",
    "attribute_scopes",
    "TimelineReport",
    "analyze",
    "analyze_logdir",
]

CLASS_COMPUTE = "compute"
CLASS_COLLECTIVE = "collective"
CLASS_MEMCPY = "memcpy"

#: op stems that move bytes without computing: host/device transfers,
#: on-device copies, infeed/outfeed. (``transpose`` is deliberately
#: compute: it burns core time, not wire.)
_MEMCPY_STEMS = frozenset({
    "copy", "copy-start", "copy-done", "infeed", "outfeed",
    "send", "send-done", "recv", "recv-done",
})

Interval = Tuple[float, float]


def op_base(name: str) -> str:
    """Instruction base of an op event name: ``%`` and the trailing
    ``.N`` ordinal stripped, lowercased (``%All-Reduce.17`` ->
    ``all-reduce``... no — ordinal only: ``all-reduce.17`` ->
    ``all-reduce``; the full name WITH ordinal is the HLO-join key, so
    this strips exactly one trailing numeric suffix)."""
    base = name.lstrip("%").lower()
    head, dot, tail = base.rpartition(".")
    if dot and tail.isdigit():
        return head
    return base


def classify_op(name: str) -> str:
    """``compute`` / ``collective`` / ``memcpy`` for one op event name.

    Collectives are matched against the HLO parser's
    :data:`COLLECTIVE_KINDS` with the async ``-start``/``-done`` forms
    normalized — the exact opcode grammar the comms differ uses, so
    "collective" means the same thing in both auditors. ``reduce.N``
    (a plain reduction) is NOT ``reduce-scatter`` and stays compute.
    """
    stem = op_base(name)
    if stem in _MEMCPY_STEMS or "memcpy" in stem:
        return CLASS_MEMCPY
    if stem.endswith("-start"):
        stem = stem[: -len("-start")]
    elif stem.endswith("-done"):
        stem = stem[: -len("-done")]
    if stem in COLLECTIVE_KINDS:
        return CLASS_COLLECTIVE
    return CLASS_COMPUTE


# -- interval algebra (all inputs/outputs in microseconds) -------------------


def merge_intervals(intervals: Sequence[Interval]) -> List[Interval]:
    """Disjoint, sorted union of ``intervals`` (zero-length dropped)."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total_us(merged: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in merged)


def intersect_intervals(
    a: Sequence[Interval], b: Sequence[Interval]
) -> List[Interval]:
    """Intersection of two MERGED interval lists."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract_intervals(
    a: Sequence[Interval], b: Sequence[Interval]
) -> List[Interval]:
    """``a`` minus ``b``, both MERGED."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def clip_intervals(
    intervals: Sequence[Interval], lo: float, hi: float
) -> List[Interval]:
    return [
        (max(a, lo), min(b, hi))
        for a, b in intervals
        if min(b, hi) > max(a, lo)
    ]


# -- async start/done fusion -------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OpInterval:
    """One classified device-op occupancy interval.

    For a fused async pair this spans launch (``-start`` begin) to
    completion (``-done`` end) and ``name`` is the ``-start``
    instruction's full name — the one the parsed :class:`HloModule`
    knows (the parser skips ``-done`` halves)."""

    cls: str
    name: str  # full instruction name, ordinal kept: "all-reduce.17"
    ts: float
    end: float

    @property
    def interval(self) -> Interval:
        return (self.ts, self.end)


def pair_async_collectives(events: Sequence[TraceEvent]) -> List[OpInterval]:
    """Classified intervals of device-op ``events``, with each async
    collective's ``-start``/``-done`` fused into one in-flight interval.

    Pairing is FIFO per ``(pid, collective kind)`` in timestamp order:
    XLA completes same-kind async ops in issue order on a device, and
    the ``-done`` instruction's ordinal does NOT match its ``-start``'s
    (so name-matching would be wrong). Unpaired halves keep their own
    span — a capture window can open between a start and its done.
    """
    out: List[OpInterval] = []
    pending: Dict[Tuple[int, str], List[TraceEvent]] = {}
    for e in sorted(events, key=lambda e: (e.ts, e.end)):
        cls = classify_op(e.name)
        stem = op_base(e.name)
        if cls == CLASS_COLLECTIVE and stem.endswith("-start"):
            pending.setdefault((e.pid, stem[:-6]), []).append(e)
            continue
        if cls == CLASS_COLLECTIVE and stem.endswith("-done"):
            queue = pending.get((e.pid, stem[:-5]), [])
            if queue:
                start = queue.pop(0)
                out.append(OpInterval(
                    cls=CLASS_COLLECTIVE,
                    name=start.name.lstrip("%"),
                    ts=start.ts,
                    end=max(e.end, start.end),
                ))
                continue
        out.append(OpInterval(
            cls=cls, name=e.name.lstrip("%"), ts=e.ts, end=e.end
        ))
    for queue in pending.values():  # starts whose done fell off the capture
        for e in queue:
            out.append(OpInterval(
                cls=CLASS_COLLECTIVE, name=e.name.lstrip("%"),
                ts=e.ts, end=e.end,
            ))
    return out


# -- per-step breakdown ------------------------------------------------------


@dataclasses.dataclass
class StepBreakdown:
    """One step's device-time partition (all times microseconds).

    Identity (test-pinned): ``compute_us + exposed_collective_us +
    exposed_memcpy_us + idle_us == span_us``.
    """

    step: int
    ts: float
    end: float
    compute_us: float
    collective_us: float
    memcpy_us: float
    exposed_collective_us: float
    exposed_memcpy_us: float
    busy_us: float
    n_ops: int

    @property
    def span_us(self) -> float:
        return self.end - self.ts

    @property
    def idle_us(self) -> float:
        return self.span_us - self.busy_us

    @property
    def bubble_fraction(self) -> float:
        return self.idle_us / self.span_us if self.span_us > 0 else 0.0

    @property
    def overlap_fraction(self) -> Optional[float]:
        """Hidden collective time / total collective time; None when the
        step ran no collectives (0/0 is not 'perfect overlap')."""
        if self.collective_us <= 0:
            return None
        return 1.0 - self.exposed_collective_us / self.collective_us


@dataclasses.dataclass
class AxisBandwidth:
    """Measured seconds joined to predicted bytes for one mesh axis."""

    axis: str
    n_events: int
    n_steps: int
    measured_us_per_step: float
    predicted_bytes_per_step: int  # ledger payload convention
    predicted_ici_bytes_per_step: int  # ring-algorithm wire bytes
    roofline_bytes_per_s: Optional[float]

    @property
    def achieved_bytes_per_s(self) -> Optional[float]:
        """Predicted wire bytes moved per measured second — the axis's
        realized bandwidth (None when nothing was measured)."""
        if self.measured_us_per_step <= 0:
            return None
        return self.predicted_ici_bytes_per_step / (
            self.measured_us_per_step * 1e-6
        )

    @property
    def utilization(self) -> Optional[float]:
        """Achieved / roofline, or None when either side is unknown —
        never a fake number (the peak-FLOPs contract)."""
        a = self.achieved_bytes_per_s
        if a is None or not self.roofline_bytes_per_s:
            return None
        return a / self.roofline_bytes_per_s


#: the label of idle time no chosen host annotation covers
NO_ANNOTATION = "(no annotation)"
#: a ``tpu_custom_call`` whose ``kernel_metadata`` names no registered
#: kernel (``goodput.scopes.KERNELS``)
UNREGISTERED_KERNEL = "(unregistered)"


@dataclasses.dataclass
class ScopeBreakdown:
    """Device self time by the program's names (microseconds, averaged
    over the devices of the capture; totals over the whole capture —
    ``per_step`` divides by :attr:`n_steps`).

    Identity (test-pinned): on a TPU, whose ops run on ONE lane a
    device, the values of :attr:`by_part` sum to :attr:`busy_us`, the
    busy union of that lane. A CPU capture runs its "device" ops on
    several threads at once, so there the self times sum to more than
    the union; shares are of :attr:`self_us` either way.
    """

    n_steps: int
    n_devices: int
    busy_us: float
    window_us: float
    #: forward / backward / grad_sync / unscale / optimizer / guard /
    #: ``(unattributed)`` -> self time
    by_part: Dict[str, float]
    #: registered kernel (or UNREGISTERED_KERNEL) -> self time, calls
    by_kernel: Dict[str, float]
    kernel_calls: Dict[str, int]
    #: kernel -> {tiles ("512x512", block_q x block_k): calls}, for the
    #: kernels whose ``kernel_metadata`` carries their tiles
    kernel_tiles: Dict[str, Dict[str, int]]
    #: (part, module path with the layer index collapsed) -> self time
    by_module: Dict[Tuple[str, str], float]
    #: (part, op name without its ordinal) -> self time
    by_op: Dict[Tuple[str, str], float]
    #: how each op was placed (scope_map's own/fused/caller/flow/none;
    #: ``event`` = by the event's own ``tf_op``, no instruction matched)
    by_how: Dict[str, float]
    #: fusions whose fused instructions span several phases (booked to
    #: the one most of them belong to; their time cannot be split):
    #: (op name without ordinal, "phase n + phase n ...") -> self time
    spanning: Dict[Tuple[str, str], float]
    #: idle time of the first device by the host annotation covering it
    idle_by_annotation: Dict[str, float]

    @property
    def self_us(self) -> float:
        return sum(self.by_part.values())

    @property
    def attributed_fraction(self) -> float:
        """Share of device self time booked to a registered phase."""
        if self.self_us <= 0:
            return 0.0
        return 1.0 - self.by_part.get(UNATTRIBUTED, 0.0) / self.self_us

    def per_step(self, us: float) -> float:
        return us / max(self.n_steps, 1)


def self_times(
    events: Sequence[TraceEvent], slack_us: float = 2e-3,
) -> List[Tuple[TraceEvent, float]]:
    """``(event, self time)`` for the events of ONE lane: each event's
    duration less the part covered by events nested in it. A ``cond`` and
    the ops of its body nest on a TPU's op lane; booking durations would
    count the body twice. Times are rounded by the exporter, so an event
    counts as nested only if it also ENDS inside its parent, give or
    take ``slack_us``; neighbours that overlap by a rounding error are
    siblings."""
    out: List[List] = []
    stack: List[List] = []  # [event, end, index into out]
    for e in sorted(events, key=lambda e: (e.ts, -e.dur)):
        while stack and (stack[-1][1] <= e.ts + slack_us
                         or e.end > stack[-1][1] + slack_us):
            stack.pop()
        if stack:
            out[stack[-1][2]][1] -= min(e.end, stack[-1][1]) - e.ts
        stack.append([e, e.end, len(out)])
        out.append([e, e.dur])
    return [(e, max(t, 0.0)) for e, t in out]


def idle_by_innermost(gaps: Sequence[Interval],
                      host: Sequence[TraceEvent]) -> Dict[str, float]:
    """Each instant of ``gaps`` (sorted, disjoint) to the innermost of the
    ``host`` annotations that cover it: of those, the one that began last
    (of two that began together, the one that ends first), named by its
    name, or ``step`` for a step marker; NO_ANNOTATION where none covers
    it. One sweep over the annotations' edges."""
    host = [h for h in host if h.end > h.ts]
    edges = sorted([(h.ts, 1, i) for i, h in enumerate(host)]
                   + [(h.end, 0, i) for i, h in enumerate(host)])
    covering: Dict[int, TraceEvent] = {}
    idle: Dict[str, float] = collections.Counter()
    k = 0
    for gs, ge in gaps:
        t = gs
        while t < ge:
            while k < len(edges) and edges[k][0] <= t:
                _, begins, i = edges[k]
                if begins:
                    covering[i] = host[i]
                else:
                    covering.pop(i, None)
                k += 1
            nxt = min(ge, edges[k][0]) if k < len(edges) else ge
            if covering:
                h = max(covering.values(), key=lambda h: (h.ts, -h.end))
                name = h.name if h.step_num is None else "step"
            else:
                name = NO_ANNOTATION
            idle[name] += nxt - t
            t = nxt
    return idle


def _scope_of_event(e: TraceEvent) -> Optional[OpScope]:
    """The scope an op event names itself: a TPU event carries its
    instruction's ``op_name`` path as ``args["tf_op"]`` (with a trailing
    ``:``) where XLA kept one. The fallback for an event no instruction
    of the compiled module matches, and the whole join when no module
    was given."""
    path = str(e.args.get("tf_op") or "").rstrip(":")
    phase, direction, module = classify_path(path)
    if phase == UNATTRIBUTED:
        return None
    return OpScope(phase=phase, direction=direction, module=module,
                   kernel=None, op_name=path, how="event")


def _kernel_of_event(
        e: TraceEvent, sc: Optional[OpScope]) -> Tuple[Optional[str], str]:
    """The Pallas kernel an op event ran and the tiles it ran with: by the
    compiled module's ``kernel_metadata``, else by the event's own text (a
    TPU event prints it); a Mosaic call that names no registered kernel is
    UNREGISTERED_KERNEL, anything else None."""
    if sc is not None and sc.kernel is not None:
        return sc.kernel, sc.tiles
    meta = e.args.get("kernel_metadata") or {}
    if meta.get("kernel") in KERNELS:
        return meta["kernel"], tiles_of(meta)
    if e.args.get("custom_call_target") == "tpu_custom_call":
        return UNREGISTERED_KERNEL, ""
    return None, ""


def attribute_scopes(
    timeline: Timeline,
    scopes: Dict[str, OpScope],
    n_steps: int,
    annotations: Sequence[str] = PHASES,
) -> Optional[ScopeBreakdown]:
    """Join the capture's device op events to ``scopes`` (a
    :func:`scope_map`) by instruction name. None when the capture holds
    no device op."""
    ops = timeline.device_op_events()
    if not ops:
        return None
    lanes: Dict[Tuple[int, int], List[TraceEvent]] = collections.defaultdict(
        list)
    for e in ops:
        lanes[(e.pid, e.tid)].append(e)
    devices = sorted({pid for pid, _ in lanes})
    n_dev = len(devices)

    by_part: Dict[str, float] = collections.Counter()
    by_kernel: Dict[str, float] = collections.Counter()
    kernel_calls: Dict[str, int] = collections.Counter()
    kernel_tiles: Dict[str, Dict[str, int]] = collections.defaultdict(
        collections.Counter)
    by_module: Dict[Tuple[str, str], float] = collections.Counter()
    by_op: Dict[Tuple[str, str], float] = collections.Counter()
    by_how: Dict[str, float] = collections.Counter()
    spanning: Dict[Tuple[str, str], float] = collections.Counter()
    for lane in lanes.values():
        for e, t in self_times(lane):
            name = e.name.lstrip("%")
            sc = scopes.get(name) or _scope_of_event(e)
            part = sc.part if sc is not None else UNATTRIBUTED
            by_part[part] += t / n_dev
            by_op[(part, op_base(name))] += t / n_dev
            by_how[sc.how if sc is not None else "no instruction"] += (
                t / n_dev)
            if sc is not None and sc.phase != UNATTRIBUTED:
                by_module[(part, sc.module)] += t / n_dev
                if sc.mix:
                    spanning[(op_base(name), " + ".join(
                        f"{p} {n}" for p, n in sc.mix))] += t / n_dev
            kernel, tiles = _kernel_of_event(e, sc)
            if kernel is not None:
                by_kernel[kernel] += t / n_dev
                kernel_calls[kernel] += 1
                if tiles:
                    kernel_tiles[kernel][tiles] += 1

    busy = {
        pid: merge_intervals([
            (e.ts, e.end) for (p, _), lane in lanes.items() if p == pid
            for e in lane
        ]) for pid in devices
    }
    wanted = set(annotations)
    # an op event is never an annotation, wherever it ran: a CPU capture
    # runs some ops on the python thread, beside its spans
    is_op = {id(e) for e in ops}
    host = [e for e in timeline.events
            if id(e) not in is_op
            and (e.name in wanted or e.step_num is not None)]
    lo = min([e.ts for e in ops] + [h.ts for h in host])
    hi = max([e.end for e in ops] + [h.end for h in host])
    idle = idle_by_innermost(
        subtract_intervals([(lo, hi)], busy[devices[0]]), host)
    return ScopeBreakdown(
        n_steps=n_steps,
        n_devices=n_dev,
        busy_us=sum(total_us(b) for b in busy.values()) / n_dev,
        window_us=hi - lo,
        by_part=dict(by_part),
        by_kernel=dict(by_kernel),
        kernel_calls=dict(kernel_calls),
        kernel_tiles={k: dict(v) for k, v in kernel_tiles.items()},
        by_module=dict(by_module),
        by_op=dict(by_op),
        by_how=dict(by_how),
        spanning=dict(spanning),
        idle_by_annotation=dict(idle),
    )


@dataclasses.dataclass
class TimelineReport:
    """The analyzer's full output: per-step partitions + the per-axis
    measured-vs-predicted bandwidth join.

    ``predicted_bubble_fraction`` (optional) is the schedule algebra's
    tick-count prediction
    (``parallel.pipeline.algebra.schedule_cost(...).bubble_fraction``):
    when the caller supplies it, every per-step ``kind="profile"``
    record carries predicted next to measured — the predicted-vs-
    measured bubble join that closes ROADMAP item 5's proof loop. The
    algebra is a dependence-graph lower bound, so on a faithful device
    capture measured >= predicted and the gap is the scheduler's
    shortfall; CPU captures undercut it (the threadpool runs different
    virtual devices' bubble ticks concurrently — the standing CPU
    caveat, docs/observability.md#timeline) and read as relative
    structure only.
    """

    steps: List[StepBreakdown]
    axes: List[AxisBandwidth]
    n_device_ops: int
    n_unattributed_collectives: int = 0
    files: List[str] = dataclasses.field(default_factory=list)
    synthetic_step: bool = False  # no markers: whole capture = one span
    predicted_bubble_fraction: Optional[float] = None
    schedule: Optional[str] = None  # algebra schedule name, when joined
    scopes: Optional[ScopeBreakdown] = None  # the join to the compiled step

    def to_records(self) -> List[dict]:
        """``kind="profile"`` records in the shared MetricRouter schema:
        one per step (milliseconds, the partition + fractions), then one
        per joined axis (stamped with the last step)."""
        from apex_tpu.monitor.router import make_record

        records = []
        for s in self.steps:
            extra = {}
            if self.predicted_bubble_fraction is not None:
                # the algebra join: predicted rides next to measured in
                # the same record so downstream consumers (the bench
                # section, the sentinel's jsonl) never re-derive it
                extra["predicted_bubble_fraction"] = (
                    self.predicted_bubble_fraction
                )
                extra["schedule"] = self.schedule
            records.append(make_record(
                "profile", s.step,
                span_ms=s.span_us / 1e3,
                compute_ms=s.compute_us / 1e3,
                collective_ms=s.collective_us / 1e3,
                exposed_comms_ms=s.exposed_collective_us / 1e3,
                memcpy_ms=s.memcpy_us / 1e3,
                exposed_memcpy_ms=s.exposed_memcpy_us / 1e3,
                idle_ms=s.idle_us / 1e3,
                overlap_fraction=s.overlap_fraction,
                bubble_fraction=s.bubble_fraction,
                n_ops=s.n_ops,
                **extra,
            ))
        last_step = self.steps[-1].step if self.steps else 0
        for ax in self.axes:
            records.append(make_record(
                "profile", last_step,
                axis=ax.axis,
                events=ax.n_events,
                measured_ms_per_step=ax.measured_us_per_step / 1e3,
                predicted_bytes=ax.predicted_bytes_per_step,
                predicted_ici_bytes=ax.predicted_ici_bytes_per_step,
                achieved_bytes_per_s=ax.achieved_bytes_per_s,
                roofline_bytes_per_s=ax.roofline_bytes_per_s,
                utilization=ax.utilization,
            ))
        sc = self.scopes
        if sc is not None:
            # one record per phase and per kernel, milliseconds a step
            for part, us in sorted(sc.by_part.items()):
                records.append(make_record(
                    "profile", last_step, part=part,
                    self_ms_per_step=sc.per_step(us) / 1e3,
                    self_fraction=us / sc.self_us if sc.self_us else None,
                ))
            for kernel, us in sorted(sc.by_kernel.items()):
                records.append(make_record(
                    "profile", last_step, kernel=kernel,
                    self_ms_per_step=sc.per_step(us) / 1e3,
                    calls=sc.kernel_calls[kernel],
                    tiles=sc.kernel_tiles.get(kernel) or None,
                ))
        return records

    def summary(self, top: int = 12) -> str:
        """The human-readable breakdown (the ``--profile-analyze``
        printout and the CLI's output)."""
        if not self.steps:
            return "timeline: no steps found (no device ops in capture)"
        lines = [
            f"timeline: {len(self.steps)} step(s), "
            f"{self.n_device_ops} device op events"
            + (" [no step markers: whole capture analyzed as one span]"
               if self.synthetic_step else "")
        ]
        for s in self.steps:
            ov = (
                f"{100 * s.overlap_fraction:5.1f}%"
                if s.overlap_fraction is not None else "    -"
            )
            lines.append(
                f"  step {s.step:4d}: span {s.span_us / 1e3:9.3f} ms | "
                f"compute {s.compute_us / 1e3:8.3f} | "
                f"collective {s.collective_us / 1e3:8.3f} "
                f"(exposed {s.exposed_collective_us / 1e3:8.3f}) | "
                f"memcpy {s.memcpy_us / 1e3:7.3f} | "
                f"idle {s.idle_us / 1e3:8.3f} "
                f"(bubble {100 * s.bubble_fraction:5.1f}%) | "
                f"overlap {ov}"
            )
        for ax in self.axes:
            a = ax.achieved_bytes_per_s
            ach = f"{a / 1e9:.3f} GB/s achieved" if a is not None else (
                "no time measured"
            )
            util = (
                f" = {100 * ax.utilization:.1f}% of ICI roofline"
                if ax.utilization is not None else
                " (roofline unknown; set APEX_TPU_ICI_BANDWIDTH)"
            )
            lines.append(
                f"  axis {ax.axis!r}: {ax.n_events} collective events, "
                f"{ax.measured_us_per_step / 1e3:.3f} ms/step measured, "
                f"{ax.predicted_ici_bytes_per_step / 2**20:.2f} MiB/step "
                f"predicted wire -> {ach}{util}"
            )
        if self.n_unattributed_collectives:
            lines.append(
                f"  ({self.n_unattributed_collectives} collective event(s) "
                f"matched no HLO instruction / axis — not joined)"
            )
        if self.scopes is not None:
            lines.extend(_scope_lines(self.scopes, top))
        if self.predicted_bubble_fraction is not None and self.steps:
            measured = sum(s.bubble_fraction for s in self.steps) / len(
                self.steps
            )
            sched = f" ({self.schedule})" if self.schedule else ""
            lines.append(
                f"  bubble join{sched}: predicted "
                f"{100 * self.predicted_bubble_fraction:5.1f}% (schedule "
                f"algebra) vs measured {100 * measured:5.1f}% (mean over "
                f"{len(self.steps)} step(s)) — gap is scheduler shortfall"
            )
        return "\n".join(lines)


def _scope_lines(sc: ScopeBreakdown, top: int = 12) -> List[str]:
    """The phase x kernel x module table of :meth:`TimelineReport.summary`
    (milliseconds a step; shares of the device's busy time)."""
    def row(label: str, us: float, extra: str = "") -> str:
        share = 100 * us / sc.self_us if sc.self_us else 0.0
        return (f"    {label:<58s} {sc.per_step(us) / 1e3:9.3f} ms "
                f"{share:5.1f}%{extra}")

    def ranked(d: dict) -> list:
        return sorted(d.items(), key=lambda kv: -kv[1])

    lines = [
        f"  device self time by the program's names: {sc.n_steps} step(s), "
        f"{sc.n_devices} device(s), self time {sc.self_us / 1e6:.6f} s "
        f"({sc.per_step(sc.self_us) / 1e3:.3f} ms a step), busy union "
        f"{sc.busy_us / 1e6:.6f} s of a {sc.window_us / 1e6:.6f} s window, "
        f"{100 * sc.attributed_fraction:.2f}% under a registered phase",
        "   by phase (forward_backward split by JAX's transpose mark):",
    ]
    lines += [row(part, us) for part, us in ranked(sc.by_part)]
    if sc.by_kernel:
        def tiles(k: str) -> str:
            ran = sc.kernel_tiles.get(k)
            if not ran:
                return ""
            if len(ran) == 1:
                return f"  tiles {next(iter(ran))}"
            return "  tiles " + ", ".join(
                f"{t} ({n / max(sc.n_steps, 1):g})" for t, n in ranked(ran))

        lines.append("   by Pallas kernel (kernel_metadata; tiles = "
                     "block_q x block_k):")
        lines += [
            row(k, us, f"  {sc.kernel_calls[k] / max(sc.n_steps, 1):g} "
                       f"calls a step{tiles(k)}")
            for k, us in ranked(sc.by_kernel)
        ]
    lines.append(f"   by module (top {top}):")
    lines += [
        row(f"{part}: {module or '-'}", us)
        for (part, module), us in ranked(sc.by_module)[:top]
    ]
    lines.append(f"   by op (top {top}):")
    lines += [
        row(f"{part}: {op}", us) for (part, op), us in ranked(sc.by_op)[:top]
    ]
    if sc.spanning:
        total = sum(sc.spanning.values())
        lines.append(
            f"   fusions that span phases (booked where most of their "
            f"instructions were traced; one pass, not separable): "
            f"{sc.per_step(total) / 1e3:.3f} ms a step")
        lines += [
            row(f"{op} [{mix}]", us)
            for (op, mix), us in ranked(sc.spanning)[:top // 2]
        ]
    lines.append(
        "   placed by: " + ", ".join(
            f"{how} {100 * us / sc.self_us:.1f}%"
            for how, us in ranked(sc.by_how)
        )
    )
    idle = sum(sc.idle_by_annotation.values())
    lines.append(
        f"   idle {idle / 1e3:.3f} ms "
        f"({100 * idle / sc.window_us if sc.window_us else 0:.2f}% of the "
        f"window) by host annotation: " + ", ".join(
            f"{name} {us / 1e3:.3f} ms"
            for name, us in ranked(sc.idle_by_annotation)
        )
    )
    return lines


def _axis_of_collective(instr, mesh, partitions) -> str:
    from apex_tpu.analysis.hlo import attribution

    if instr.kind == "collective-permute":
        return attribution.classify_source_target_pairs(
            mesh, instr.source_target_pairs, partitions
        )
    return attribution.classify_replica_groups(
        mesh, instr.replica_groups, partitions
    )


def _predicted_per_axis(ledger, mesh) -> Dict[str, Dict[str, int]]:
    """The ledger's per-axis totals re-keyed onto attribution labels
    (size-1 axes dropped, mesh order) so both join sides bucket
    identically — the comms differ's canon rule."""
    from apex_tpu.analysis.hlo import attribution

    out: Dict[str, Dict[str, int]] = {}
    for axis, d in ledger.per_axis().items():
        key = attribution.canon_axis_key(mesh, axis)
        if key == attribution.AXIS_NONE:
            continue
        agg = out.setdefault(key, {"bytes": 0, "ici_bytes": 0})
        agg["bytes"] += d["bytes"]
        agg["ici_bytes"] += d["ici_bytes"]
    return out


def analyze(
    timeline: Timeline,
    module=None,
    mesh=None,
    ledger=None,
    ici_bandwidth: Optional[float] = None,
    predicted_bubble_fraction: Optional[float] = None,
    schedule: Optional[str] = None,
    annotations: Sequence[str] = PHASES,
) -> TimelineReport:
    """Compute the full report from one parsed capture.

    ``module`` (a parsed :class:`HloModule`), ``mesh``, and ``ledger``
    (a :class:`CommsLedger`, e.g. from ``xray.predict_comms``) enable
    the bandwidth join; without them only the per-step partition is
    produced. ``ici_bandwidth`` (bytes/s per chip) enables the
    utilization column — pass
    ``xray.ledger.ici_bandwidth_per_device()`` or a pinned number; the
    analyzer itself never guesses one.

    ``module`` alone (no mesh: a bare capture plus the compiled step's
    text) enables the scope join: device self time by step phase, Pallas
    kernel and flax module (:class:`ScopeBreakdown`). ``annotations``
    names the host ``TraceAnnotation`` spans the device's idle gaps are
    put down to — the goodput phases by default, which
    ``goodput.span`` opens on the profiler's clock.

    ``predicted_bubble_fraction`` / ``schedule`` attach the pipeline
    schedule algebra's prediction
    (``parallel.pipeline.algebra.schedule_cost``) to every per-step
    record and the summary — the predicted-vs-measured bubble join (see
    :class:`TimelineReport`); the analyzer never derives a prediction
    itself (it cannot know (P, M, V)).
    """
    ops = timeline.device_op_events()
    intervals = pair_async_collectives(ops)
    spans = timeline.step_spans()
    synthetic = False
    if not spans and intervals:
        synthetic = True
        spans = [StepSpan(
            step=-1,
            ts=min(o.ts for o in intervals),
            end=max(o.end for o in intervals),
        )]

    by_class: Dict[str, List[Interval]] = {
        CLASS_COMPUTE: [], CLASS_COLLECTIVE: [], CLASS_MEMCPY: [],
    }
    for o in intervals:
        by_class[o.cls].append(o.interval)

    steps: List[StepBreakdown] = []
    for span in spans:
        comp = merge_intervals(
            clip_intervals(by_class[CLASS_COMPUTE], span.ts, span.end)
        )
        coll = merge_intervals(
            clip_intervals(by_class[CLASS_COLLECTIVE], span.ts, span.end)
        )
        memc = merge_intervals(
            clip_intervals(by_class[CLASS_MEMCPY], span.ts, span.end)
        )
        busy = merge_intervals(list(comp) + list(coll) + list(memc))
        n_ops = sum(
            1 for o in intervals if o.end > span.ts and o.ts < span.end
        )
        steps.append(StepBreakdown(
            step=span.step,
            ts=span.ts,
            end=span.end,
            compute_us=total_us(comp),
            collective_us=total_us(coll),
            memcpy_us=total_us(memc),
            exposed_collective_us=total_us(
                subtract_intervals(coll, comp)
            ),
            exposed_memcpy_us=total_us(subtract_intervals(
                memc, merge_intervals(list(comp) + list(coll))
            )),
            busy_us=total_us(busy),
            n_ops=n_ops,
        ))

    axes: List[AxisBandwidth] = []
    unattributed = 0
    if module is not None and mesh is not None and steps:
        from apex_tpu.analysis.hlo import attribution

        partitions = attribution.mesh_axis_partitions(mesh)
        instr_by_name = {c.name.lstrip("%"): c for c in module.collectives}
        axis_intervals: Dict[str, List[Interval]] = {}
        axis_events: Dict[str, int] = {}
        for o in intervals:
            if o.cls != CLASS_COLLECTIVE:
                continue
            instr = instr_by_name.get(o.name)
            axis = (
                _axis_of_collective(instr, mesh, partitions)
                if instr is not None else None
            )
            if axis is None or axis in (
                attribution.AXIS_NONE, attribution.AXIS_UNKNOWN
            ):
                unattributed += 1
                continue
            axis_intervals.setdefault(axis, []).append(o.interval)
            axis_events[axis] = axis_events.get(axis, 0) + 1
        predicted = (
            _predicted_per_axis(ledger, mesh) if ledger is not None else {}
        )
        for axis in sorted(set(axis_intervals) | set(predicted)):
            measured = 0.0
            for span in spans:
                measured += total_us(merge_intervals(clip_intervals(
                    axis_intervals.get(axis, []), span.ts, span.end
                )))
            pred = predicted.get(axis, {"bytes": 0, "ici_bytes": 0})
            axes.append(AxisBandwidth(
                axis=axis,
                n_events=axis_events.get(axis, 0),
                n_steps=len(steps),
                measured_us_per_step=measured / len(steps),
                predicted_bytes_per_step=pred["bytes"],
                predicted_ici_bytes_per_step=pred["ici_bytes"],
                roofline_bytes_per_s=ici_bandwidth,
            ))

    scopes = None
    has_text = module is not None and getattr(module, "text", "")
    if has_text or any("tf_op" in e.args for e in ops):
        scopes = attribute_scopes(
            timeline, scope_map(module) if has_text else {},
            n_steps=(timeline.program_runs() if synthetic else len(steps)),
            annotations=annotations,
        )

    return TimelineReport(
        steps=steps,
        axes=axes,
        n_device_ops=len(ops),
        n_unattributed_collectives=unattributed,
        synthetic_step=synthetic,
        predicted_bubble_fraction=predicted_bubble_fraction,
        schedule=schedule,
        scopes=scopes,
    )


def analyze_logdir(
    logdir: str,
    module=None,
    mesh=None,
    ledger=None,
    ici_bandwidth: Optional[float] = None,
    predicted_bubble_fraction: Optional[float] = None,
    schedule: Optional[str] = None,
    annotations: Sequence[str] = PHASES,
) -> TimelineReport:
    """Parse the newest capture under ``logdir`` and :func:`analyze` it
    (the ``--profile-analyze`` and CLI entry path)."""
    timeline, files = parse_logdir(logdir)
    report = analyze(
        timeline, module=module, mesh=mesh, ledger=ledger,
        ici_bandwidth=ici_bandwidth,
        predicted_bubble_fraction=predicted_bubble_fraction,
        schedule=schedule, annotations=annotations,
    )
    report.files = files
    return report
