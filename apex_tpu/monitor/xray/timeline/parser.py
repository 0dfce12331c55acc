"""Profiler trace-event reader: the one blessed home of ``*.trace.json``.

``ProfilerTrigger`` and ``utils.trace`` write ``jax.profiler`` captures
under ``<logdir>/plugins/profile/<run>/`` — one ``<host>.trace.json.gz``
per host in Chrome trace-event format, next to the ``.xplane.pb`` raw
protos. This module is the only place that format is parsed (the
``lint.trace-file`` rule pins that, same contract as ``lint.hlo-text``
and the HLO parser): ad-hoc readers of profiler output rot the moment
XProf's exporter changes, so every consumer goes through the structured
records here.

Same no-heavy-import discipline as ``analysis/hlo/parser.py``: gzip +
json + dataclasses only — a trace file is analyzable on any box, no jax
(or device) required.

What the reader understands, verified against this container's XProf
exporter (and deliberately nothing more):

- top level ``{"traceEvents": [...], "displayTimeUnit": ...}``;
  ``ts``/``dur`` are MICROSECONDS (the Chrome trace convention,
  regardless of displayTimeUnit);
- metadata events (``ph="M"``): ``process_name`` / ``thread_name`` with
  ``args.name`` — lane labels;
- complete events (``ph="X"``): ``name``, ``pid``, ``tid``, ``ts``,
  ``dur``, ``args``. Three event classes matter downstream:

  - **step markers** — ``jax.profiler.StepTraceAnnotation`` spans carry
    ``args["step_num"]`` (a STRING in the wire format); they live on the
    host thread that ran the step loop.
  - **XLA op executions** — events carrying ``args["hlo_op"]`` (CPU
    backend; ``args["hlo_module"]`` names the module) or living on a
    ``/device:...`` process (TPU). Their names are HLO instruction
    names (``all-reduce.1``, ``fusion.42``) — joinable against a parsed
    ``HloModule``'s instructions by exact instruction name. A TPU
    (v5e) event carries NO ``hlo_op`` argument. What it carries is the
    instruction's whole HLO text (``%self_attention.117 = (bf16[...])
    custom-call(...), custom_call_target="tpu_custom_call",
    frontend_attributes={kernel_metadata={...}}``, no ``metadata=``):
    as ``args["long_name"]`` in this export, beside the short name, the
    ``op_name`` path as ``args["tf_op"]`` (where XLA kept one: a third
    of the events, 7% of the time, have none) and ``hlo_category``; as
    the event's NAME in the raw ``.xplane.pb``. The reader takes either:
    the instruction's name is the event's name and ``args`` gains the
    ``opcode``, the ``custom_call_target`` and the ``kernel_metadata``
    the text held (parsed by ``analysis/hlo/parser.py``, the one home
    of HLO text).
  - everything else (python frames, runtime bookkeeping like
    ``ThreadpoolListener::*``) — host noise the analyzer ignores.

Timestamps across threads of one capture share a clock, but a few
runtime-thread events can carry stale (pre-capture) timestamps —
observed in this container's CPU captures. The analyzer only attributes
events intersecting a step span, which drops the strays naturally.
"""

import dataclasses
import gzip
import json
import os
from typing import Dict, List, Optional, Tuple

from apex_tpu.analysis.hlo.parser import parse_instruction

__all__ = [
    "TraceEvent",
    "StepSpan",
    "Timeline",
    "find_trace_files",
    "load_trace_json",
    "parse_trace",
    "parse_trace_file",
    "parse_logdir",
]

#: filename suffixes of the trace-event export (gzipped and plain)
TRACE_SUFFIXES = (".trace.json.gz", ".trace.json")

#: a TPU device process's lanes: one event per executed HLO op, and one
#: per program execution
OPS_LANE = "XLA Ops"
MODULES_LANE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One complete (``ph="X"``) trace event; times in microseconds."""

    name: str
    pid: int
    tid: int
    ts: float
    dur: float
    args: dict = dataclasses.field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.ts + self.dur

    @property
    def hlo_op(self) -> Optional[str]:
        """The HLO instruction name when this is an XLA op execution."""
        op = self.args.get("hlo_op")
        return str(op) if op is not None else None

    @property
    def step_num(self) -> Optional[int]:
        """The step number when this is a StepTraceAnnotation span."""
        v = self.args.get("step_num")
        if v is None:
            return None
        try:
            return int(v)  # the exporter stringifies it
        except (TypeError, ValueError):
            return None


@dataclasses.dataclass(frozen=True)
class StepSpan:
    """One segmented step: the wall-clock window of ``step_num``."""

    step: int
    ts: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.ts


@dataclasses.dataclass
class Timeline:
    """One capture's events plus its lane labels."""

    events: List[TraceEvent]
    process_names: Dict[int, str]
    thread_names: Dict[Tuple[int, int], str]

    def lane(self, e: TraceEvent) -> str:
        """Human label of the event's lane: ``process/thread``."""
        proc = self.process_names.get(e.pid, str(e.pid))
        thread = self.thread_names.get((e.pid, e.tid), str(e.tid))
        return f"{proc}/{thread}"

    def step_spans(self) -> List[StepSpan]:
        """StepTraceAnnotation windows, ordered by start time. Repeated
        step numbers (two captures in one file) stay distinct spans."""
        spans = [
            StepSpan(step=e.step_num, ts=e.ts, end=e.end)
            for e in self.events
            if e.step_num is not None
        ]
        return sorted(spans, key=lambda s: (s.ts, s.step))

    def device_op_events(self) -> List[TraceEvent]:
        """The XLA op executions — the device-time ground truth.

        Two detection paths, in preference order:

        1. events carrying ``args["hlo_op"]`` (the CPU backend's
           exporter; exact and lane-agnostic);
        2. if none exist but some process is named ``/device:...``
           (TPU), every complete event on those processes whose thread
           is THE op lane (``XLA Ops``; not ``Async XLA Ops``, whose
           copies overlap the ops and occupy no core) — or all
           device-process events when no lane carries that label.

        A device event that is ALSO a step marker is never an op.
        """
        ops = [
            e for e in self.events
            if e.hlo_op is not None and e.step_num is None
        ]
        if ops:
            return ops
        device_pids = {
            pid for pid, name in self.process_names.items()
            if "/device:" in name
        }
        if not device_pids:
            return []
        on_device = [
            e for e in self.events
            if e.pid in device_pids and e.step_num is None
        ]
        op_lanes = [
            e for e in on_device
            if self.thread_names.get((e.pid, e.tid), "").strip() == OPS_LANE
        ]
        return op_lanes or on_device

    def program_runs(self) -> int:
        """How many times the capture's busiest program ran on one
        device: the events of the TPU's ``XLA Modules`` lane (one per
        program execution), counted for the name with the most device
        time, on the first device. A capture without step markers is
        segmented by it; 1 where no such lane exists (a CPU capture)."""
        runs: Dict[Tuple[int, str], List[float]] = {}
        for e in self.events:
            if (self.thread_names.get((e.pid, e.tid), "").strip()
                    == MODULES_LANE):
                runs.setdefault((e.pid, e.name), []).append(e.dur)
        if not runs:
            return 1
        first = min(pid for pid, _ in runs)
        return max(
            (sum(durs), len(durs)) for (pid, _), durs in runs.items()
            if pid == first
        )[1]

    def merged(self, other: "Timeline") -> "Timeline":
        """This capture plus ``other`` (a second host's file of the same
        run). Lane keys may collide across hosts; events keep their own
        pid/tid and the first host's labels win on collision."""
        return Timeline(
            events=self.events + other.events,
            process_names={**other.process_names, **self.process_names},
            thread_names={**other.thread_names, **self.thread_names},
        )


def find_trace_files(logdir: str) -> List[str]:
    """Every trace-event file under ``logdir``, newest capture first.

    ``jax.profiler`` nests captures as ``plugins/profile/<timestamp>/``;
    sorting by the containing directory name (the timestamp) descending,
    then by filename, returns the most recent capture's hosts first.
    """
    found = []
    for dirpath, _, names in os.walk(logdir):
        for fn in sorted(names):
            if fn.endswith(TRACE_SUFFIXES):
                found.append(os.path.join(dirpath, fn))
    return sorted(
        found, key=lambda p: (os.path.dirname(p), os.path.basename(p)),
        reverse=True,
    )


def load_trace_json(path: str) -> dict:
    """The raw trace dict of one ``*.trace.json[.gz]`` file."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _split_hlo_text(raw: str, seen: Dict[str, Tuple[str, dict]]):
    """``(instruction name, parsed args)`` of an instruction's whole HLO
    text (a TPU op event's ``long_name``, or its raw name);
    ``(raw, {})`` for any other string. A step repeats its instructions,
    so texts are parsed once (``seen``)."""
    if not raw.startswith("%") or " = " not in raw:
        return raw, {}
    if raw not in seen:
        ins = parse_instruction(raw)
        if ins is None:
            seen[raw] = (raw, {})
        else:
            extra = {"opcode": ins.opcode}
            if ins.custom_call_target:
                extra["custom_call_target"] = ins.custom_call_target
            if ins.kernel_metadata:
                extra["kernel_metadata"] = dict(ins.kernel_metadata)
            seen[raw] = (ins.name, extra)
    return seen[raw]


def parse_trace(data: dict) -> Timeline:
    """Structure one loaded trace dict (tests inject synthetic dicts
    here — the same seam as ``parse_hlo_module`` taking text)."""
    raw = data.get("traceEvents")
    if not isinstance(raw, list):
        raise ValueError(
            "not a trace-event export: no traceEvents list "
            "(schema drift? this parser understands the Chrome "
            "trace-event format jax.profiler writes)"
        )
    events: List[TraceEvent] = []
    process_names: Dict[int, str] = {}
    thread_names: Dict[Tuple[int, int], str] = {}
    hlo_texts: Dict[str, Tuple[str, dict]] = {}
    for e in raw:
        if not isinstance(e, dict):
            continue
        ph = e.get("ph")
        if ph == "M":
            args = e.get("args") or {}
            if e.get("name") == "process_name" and "name" in args:
                process_names[int(e.get("pid", 0))] = str(args["name"])
            elif e.get("name") == "thread_name" and "name" in args:
                thread_names[
                    (int(e.get("pid", 0)), int(e.get("tid", 0)))
                ] = str(args["name"])
        elif ph == "X" and "ts" in e:
            args = e.get("args") or {}
            name, parsed = _split_hlo_text(str(e.get("name", "")), hlo_texts)
            if not parsed and isinstance(args.get("long_name"), str):
                parsed = _split_hlo_text(args["long_name"], hlo_texts)[1]
            events.append(TraceEvent(
                name=name,
                pid=int(e.get("pid", 0)),
                tid=int(e.get("tid", 0)),
                ts=float(e["ts"]),
                dur=float(e.get("dur", 0.0)),
                args={**args, **parsed} if parsed else args,
            ))
    return Timeline(
        events=events,
        process_names=process_names,
        thread_names=thread_names,
    )


def parse_trace_file(path: str) -> Timeline:
    return parse_trace(load_trace_json(path))


def parse_logdir(logdir: str) -> Tuple[Timeline, List[str]]:
    """Parse the NEWEST capture under ``logdir`` (all its hosts' files
    merged into one Timeline). Returns ``(timeline, files_used)``;
    raises ``FileNotFoundError`` when no trace file exists.

    Only one capture is merged: mixing two captures' clocks would make
    every duration nonsense. The newest-first ordering of
    :func:`find_trace_files` makes "the capture just taken" the default.
    """
    files = find_trace_files(logdir)
    if not files:
        raise FileNotFoundError(
            f"no *.trace.json[.gz] under {logdir!r} — is this a "
            f"jax.profiler log dir (plugins/profile/<run>/...)?"
        )
    newest_run = os.path.dirname(files[0])
    used = [p for p in files if os.path.dirname(p) == newest_run]
    timeline = parse_trace_file(used[0])
    for path in used[1:]:
        timeline = timeline.merged(parse_trace_file(path))
    return timeline, used
