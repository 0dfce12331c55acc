"""From a profiler trace to numbers: busy union, idle share, time by op name,
idle gaps attributed to the host span they fall in.

Two steps, so that the arithmetic can be checked on a small recorded trace
with no profiler: ``load_xplane`` turns the ``.xplane.pb`` the JAX profiler
writes into plain event dicts (``plane``, ``line``, ``name``, ``start_ns``,
``dur_ns``, and for device events the few string stats that tell kernels
apart), and ``reduce`` works on those alone.

What a TPU trace looks like (checked by hand on a v5e trace, PERF.md 5): one
plane per chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event
per executed HLO op, named by the op's whole HLO text (``%self_attention.117 =
(bf16[2,64,1024,64]...) custom-call(...), custom_call_target="tpu_custom_call"``:
the instruction's name carries the flax module's scope; what the program
called a Pallas kernel rides in the text's ``kernel_metadata``). Nested ops such as a
``cond`` and its body overlap: the busy time is the UNION of the intervals, and
time by name is each event's SELF time (its duration less what nests in it).
Beside it lie ``XLA Modules`` (one event per program run), ``Steps`` and
``Async XLA Ops`` (copies that overlap the ops; not counted as busy). ``/host:CPU`` holds the host's thread
lines with the ``TraceAnnotation`` spans.

``python perf/trace_reduce.py <file.xplane.pb>`` prints what a trace holds,
for the look by hand that has to come before any new reader.
"""

import bisect
import collections
import heapq
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_KEPT_STATS = ("tf_op", "hlo_category", "long_name", "kernel_details",
               "hlo_op", "hlo_module", "name")


_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KERNEL_PAIRS = re.compile(r'kernel_metadata=\{([^{}]*)\}')
_JSON_PAIR = re.compile(r'"((?:[^"\\]|\\.)*)"\s*:\s*"((?:[^"\\]|\\.)*)"')
MODULES_LINE = "XLA Modules"
MOSAIC_TARGET = "tpu_custom_call"
UNATTRIBUTED = "(unattributed)"
_RUN_ID = re.compile(r"\(\d+\)$")


def split_hlo(raw):
    """An ``XLA Ops`` event's name is the op's HLO text. Returns the
    instruction's own name (``self_attention.117``) and what else of the text
    tells kernels apart: the custom-call target, the head of the result
    shape, and what the program itself called a Pallas kernel:
    ``frontend_attributes={kernel_metadata={"kernel": "flash_fwd", "block_q":
    "1024", ...}}`` (flat JSON of strings, printed over several lines), as a
    dict under ``kernel_metadata``."""
    name, sep, rest = raw.partition(" = ")
    stats = {}
    if sep:
        stats["result"] = rest[:80]
        m = _TARGET.search(rest)
        if m:
            stats["custom_call_target"] = m.group(1)
        m = _KERNEL_PAIRS.search(rest)
        if m:
            stats["kernel_metadata"] = dict(_JSON_PAIR.findall(m.group(1)))
    return name.lstrip("%"), stats


def base_name(name):
    """``fusion.895`` -> ``fusion``: the name without XLA's numbering."""
    return re.sub(r"(\.\d+)+$", "", name)


def load_xplane(path):
    """Event dicts from an ``.xplane.pb``: the device planes' and the host
    planes' events."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        if not on_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                e = {"plane": plane.name, "line": line.name, "name": ev.name,
                     "start_ns": float(ev.start_ns),
                     "dur_ns": float(ev.duration_ns)}
                if on_device and line.name == OPS_LINE:
                    e["name"], stats = split_hlo(ev.name)
                    for k, v in ev.stats:
                        if k in _KEPT_STATS and isinstance(v, str):
                            stats[k] = v[:300]
                    if stats:
                        e["stats"] = stats
                events.append(e)
    return events


def union(intervals):
    """Merged, sorted, non-overlapping [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events, slack_ns=2.0):
    """``(event, self time in ns)`` for the events of ONE line of one plane:
    each event's duration less the part that events nested in it cover. A
    ``cond`` and the ops of its body nest, and so does a nanosecond-long
    custom-call that the scheduler put inside a kernel's interval: booking
    whole durations would count the body twice, booking innermost events
    alone would lose the kernel. The self times of a line add up to its busy
    union. Times come in picoseconds and are read as float nanoseconds, so
    neighbours can overlap by a rounding error: an event counts as inside
    another only if it also ENDS inside it, give or take ``slack_ns``."""
    out, stack = [], []  # stack: [end, index into out]
    for e in sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        end = e["start_ns"] + e["dur_ns"]
        while stack and (stack[-1][0] <= e["start_ns"] + slack_ns
                         or end > stack[-1][0] + slack_ns):
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(end, stack[-1][0]) - e["start_ns"]
        stack.append([end, len(out)])
        out.append([e, e["dur_ns"]])
    return [(e, max(t, 0.0)) for e, t in out]


def module_runs(events, planes):
    """How often the step's program ran on a chip in the trace: the events
    of the ``XLA Modules`` line (one per program run) of the program that
    took most of the time there, averaged over ``planes``. 0 where the line
    is missing."""
    secs, runs = collections.Counter(), collections.Counter()
    for e in events:
        if e["line"] == MODULES_LINE and e["plane"] in planes:
            secs[e["name"]] += e["dur_ns"]
            runs[e["name"]] += 1
    if not secs:
        return 0.0
    return runs[max(secs, key=secs.get)] / float(len(planes))


def program_name(module_event_name):
    """``jit_decode(1234567)`` -> ``jit_decode``: the name of a program
    without the id of its compiled module (the prefill buckets are several
    programs of one name, and read as one)."""
    return _RUN_ID.sub("", module_event_name)


def module_line(events, plane):
    """The runs on ``plane``'s ``XLA Modules`` line, by start: their starts,
    and each run's (start, end, ``program_name``)."""
    runs = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"],
                   program_name(e["name"])) for e in events
                  if e["plane"] == plane and e["line"] == MODULES_LINE)
    return [r[0] for r in runs], runs


def program_at(starts, runs, t):
    """The program whose run (``module_line``) holds the instant ``t`` (an
    op's start, give or take a nanosecond of rounding), None outside every
    run. A TPU trace names no program on an op's event: the run that holds
    it is what ties an op to its program."""
    i = bisect.bisect_right(starts, t + 1.0) - 1
    return runs[i][2] if i >= 0 and t < runs[i][1] else None


def program_runs(events, planes):
    """How often each program (``program_name``) ran on a chip: its events
    on the ``XLA Modules`` line, averaged over ``planes``."""
    runs = collections.Counter()
    for e in events:
        if e["line"] == MODULES_LINE and e["plane"] in planes:
            runs[program_name(e["name"])] += 1
    return {k: v / float(len(planes)) for k, v in runs.items()}


def _gap_spans(gaps, host):
    """For each gap ``(start, end)`` of ``gaps`` (ascending, disjoint), the
    name of the host span that overlaps it most: the strictly largest
    overlap wins, a tie goes to the span first in ``host``'s order, and
    ``(no span)`` where no overlap is positive. One sweep: spans enter by
    start as the gaps' ends pass them and leave, by end, once a gap starts
    after them, so each gap scores only the spans that overlap it, in
    ``host``'s order and with the overlap as the plain double loop over gaps
    and spans computes it."""
    order = sorted(range(len(host)), key=lambda i: host[i]["start_ns"])
    ends = [h["start_ns"] + h["dur_ns"] for h in host]
    active, nxt, out = [], 0, []  # active: heap of (end, index into host)
    for gs, ge in gaps:
        while nxt < len(order) and host[order[nxt]]["start_ns"] < ge:
            heapq.heappush(active, (ends[order[nxt]], order[nxt]))
            nxt += 1
        while active and active[0][0] <= gs:
            heapq.heappop(active)
        best, best_ov = "(no span)", 0.0
        for i in sorted(i for _, i in active):
            ov = min(ge, ends[i]) - max(gs, host[i]["start_ns"])
            if ov > best_ov:
                best, best_ov = host[i]["name"], ov
        out.append(best)
    return out


def _book(sc, secs, phase_seconds, scope_seconds):
    """An op's self time under its ``Scope``'s phase (``(unattributed)``
    where the text has no such instruction) and model scope, if any."""
    phase_seconds[sc.part if sc else UNATTRIBUTED] += secs
    if sc and sc.scope:
        scope_seconds[sc.scope] += secs


def reduce(events, chips=1, spans=(), device_required=True, scopes=None,
           program_scopes=None):
    """The reduction the per-layer metrics read.

    ``window_s``: first to last instant of anything kept (device ops and the
    named host spans). ``busy_s``: union of device-op intervals, averaged
    over the ``chips`` first device planes. ``op_seconds``: summed self time
    (``_self_times``) of device ops by name, over those planes. ``op_counts``:
    how many events with any.
    ``op_stats``: for each op name, the string stats its first event
    carried. ``top_ops``: the ten
    longest of ``op_seconds`` grouped by name without XLA's numbering. ``idle_gaps``: idle time on the first chip by
    the host span that overlaps each gap most (``(no span)`` where none
    does), longest first. ``span_seconds``: host time by span name.

    ``steps``: runs of the step's program a chip (``module_runs``).
    ``kernel_seconds`` / ``kernel_counts``: ops' self time and number by the
    kernel their ``kernel_metadata`` names, over the planes; a Mosaic
    custom-call that carries none (the library's grouped matmuls) by its
    instruction's name without XLA's numbering. With ``scopes`` (instruction
    name -> ``hlo_scopes.Scope``, from the compiled step's text) also
    ``phase_seconds`` (ops' self time by ``Scope.part``; an op no
    instruction of the text matches under ``(unattributed)``) and
    ``scope_seconds`` (by model scope, ops under none left out), over the
    planes; without, both are empty.

    With ``program_scopes`` (program name -> such a map, from that
    program's own compiled text: a serving engine runs several programs,
    whose instruction names collide) also ``programs``: for every program
    on the ``XLA Modules`` line its ``runs`` a chip (``program_runs``), and
    for each that has a map its ``phase_seconds`` and ``scope_seconds`` as
    above. An op is booked by the map of the program whose run holds it
    (``program_at``), never by another's; the ops of a program without a map
    are booked nowhere. Nothing else of the reduction changes."""
    planes = sorted({e["plane"] for e in events
                     if DEVICE_PLANE.match(e["plane"])},
                    key=lambda p: int(DEVICE_PLANE.match(p).group(1)))[:chips]
    span_names = set(spans)
    host = [e for e in events if e["plane"].startswith("/host:")
            and e["name"] in span_names]
    if not planes:
        if device_required:
            raise ValueError("the trace holds no device plane: no operation "
                             "ran on the device in the traced window")
        # a CPU run (a test): host spans only, nothing said of a device
        span_seconds = collections.Counter()
        for h in host:
            span_seconds[h["name"]] += h["dur_ns"] * 1e-9
        ends = [(h["start_ns"], h["start_ns"] + h["dur_ns"]) for h in host]
        out = {"window_s": (max(e for _, e in ends)
                            - min(s for s, _ in ends)) * 1e-9 if ends else 0.0,
               "busy_s": None, "chips": 0, "op_seconds": {}, "op_stats": {},
               "op_counts": {}, "top_ops": [], "idle_gaps": [],
               "span_seconds": dict(span_seconds), "steps": 0.0,
               "kernel_seconds": {}, "kernel_counts": {},
               "phase_seconds": {}, "scope_seconds": {}}
        if program_scopes is not None:
            out["programs"] = {}
        return out
    dev = {p: [e for e in events
               if e["plane"] == p and e["line"] == OPS_LINE] for p in planes}
    if not any(dev.values()):
        raise ValueError(f"no events on the {OPS_LINE!r} line of {planes}")
    everything = host + [e for evs in dev.values() for e in evs]
    t0 = min(e["start_ns"] for e in everything)
    t1 = max(e["start_ns"] + e["dur_ns"] for e in everything)

    busy, op_seconds, op_stats = [], collections.Counter(), {}
    op_counts = collections.Counter()
    kernel_seconds, kernel_counts = collections.Counter(), collections.Counter()
    phase_seconds, scope_seconds = collections.Counter(), collections.Counter()
    by_program = collections.defaultdict(lambda: (collections.Counter(),
                                                  collections.Counter()))
    merged_first = None
    for p in planes:
        merged = union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                        for e in dev[p]])
        if merged_first is None:
            merged_first = merged
        busy.append(sum(e - s for s, e in merged))
        if program_scopes is not None:
            starts, runs = module_line(events, p)
        for e, self_ns in _self_times(dev[p]):
            if self_ns <= 0:
                continue  # a ``while`` whose body did all the work
            secs, stats = self_ns * 1e-9, e.get("stats", {})
            op_seconds[e["name"]] += secs
            op_counts[e["name"]] += 1
            if stats:
                op_stats.setdefault(e["name"], stats)
            kernel = stats.get("kernel_metadata", {}).get("kernel")
            if kernel is None and stats.get(
                    "custom_call_target") == MOSAIC_TARGET:
                kernel = base_name(e["name"])
            if kernel is not None:
                kernel_seconds[kernel] += secs
                kernel_counts[kernel] += 1
            if scopes is not None:
                _book(scopes.get(e["name"]), secs, phase_seconds,
                      scope_seconds)
            if program_scopes is not None:
                program = program_at(starts, runs, e["start_ns"])
                if program in program_scopes:
                    _book(program_scopes[program].get(e["name"]), secs,
                          *by_program[program])

    gaps, cursor = [], t0
    for s, e in merged_first + [[t1, t1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    by_span = collections.Counter()
    for (gs, ge), best in zip(gaps, _gap_spans(gaps, host)):
        by_span[best] += (ge - gs) * 1e-9
    span_seconds = collections.Counter()
    for h in host:
        span_seconds[h["name"]] += h["dur_ns"] * 1e-9

    grouped = collections.Counter()
    for name, secs in op_seconds.items():
        grouped[base_name(name)] += secs
    ranked = sorted(grouped.items(), key=lambda kv: -kv[1])
    out = {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy) * 1e-9 / len(planes),
        "chips": len(planes),
        "op_seconds": dict(op_seconds),
        "op_stats": op_stats,
        "op_counts": dict(op_counts),
        "top_ops": [[k, v] for k, v in ranked[:10]],
        "idle_gaps": [[k, v] for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])],
        "span_seconds": dict(span_seconds),
        "steps": module_runs(events, planes),
        "kernel_seconds": dict(kernel_seconds),
        "kernel_counts": dict(kernel_counts),
        "phase_seconds": dict(phase_seconds),
        "scope_seconds": dict(scope_seconds),
    }
    if program_scopes is not None:
        runs = program_runs(events, planes)
        out["programs"] = {
            p: {"runs": runs.get(p, 0.0),
                "phase_seconds": dict(by_program[p][0]),
                "scope_seconds": dict(by_program[p][1])}
            for p in sorted(set(runs) | set(by_program))}
    return out


def per_step_ms(reduction, table, names):
    """Milliseconds a step a chip that a reduction booked to ``names`` in
    ``table`` (``phase_seconds``, ``scope_seconds``, ``kernel_seconds``:
    summed over the chips, so divided by them and by a chip's ``steps``).
    None where the trace holds no whole step or nothing under those names:
    nothing to read is not 0."""
    if not reduction or not reduction["steps"]:
        return None
    secs = sum(reduction[table].get(n, 0.0) for n in names)
    if secs <= 0:
        return None
    return 1e3 * secs / reduction["chips"] / reduction["steps"]


def per_run_ms(reduction, program, table, names):
    """Milliseconds a run a chip of ``program`` that a reduction booked to
    ``names`` in that program's ``table`` (``phase_seconds``,
    ``scope_seconds``; ``reduce``'s ``programs``): ``per_step_ms`` for a
    reader of one program of several, such as a serving engine's decode
    step. None where the trace holds no run of it, no map of it, or nothing
    under those names: nothing to read is not 0."""
    p = ((reduction or {}).get("programs") or {}).get(program)
    if not p or not p["runs"]:
        return None
    secs = sum(p[table].get(n, 0.0) for n in names)
    if secs <= 0:
        return None
    return 1e3 * secs / reduction["chips"] / p["runs"]


def describe(path, top=40):
    """What a trace holds: planes, lines, event counts, the longest op names
    with their stats. For reading by hand."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            total = sum(e.duration_ns for e in evs) * 1e-9
            lines.append(f"  LINE {line.name!r}: {len(evs)} events, "
                         f"{total:.4f} s summed")
            if not DEVICE_PLANE.match(plane.name) and len(evs) > 2000:
                continue
            by = collections.Counter()
            first = {}
            for e in evs:
                by[e.name] += e.duration_ns * 1e-9
                first.setdefault(e.name, e)
            for name, secs in by.most_common(top):
                stats = {k: (v[:120] if isinstance(v, str) else v)
                         for k, v in first[name].stats}
                lines.append(f"    {secs:10.6f} s  {name[:100]}  "
                             f"{json.dumps(stats, default=str)[:400]}")
    return "\n".join(lines)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
