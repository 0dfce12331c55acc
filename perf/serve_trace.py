"""Device time by PROGRAM, from the profiler's events: the reduction a
serving cell's readers need beside ``trace_reduce.reduce``'s.

A training window runs one program; a serving engine runs several (a prefill
per prompt bucket, one decode step), and what a reader asks is how long one
run of the decode program takes, which ops that time is in, and what share
of the device's busy time prefills take. The ``XLA Modules`` line of a
device plane holds one event per program run, named ``jit_<function>(<id>)``;
every ``XLA Ops`` event lies inside one of them. ``by_program`` books each
op's SELF time (``trace_reduce._self_times``: its duration less what nests in
it, so a program's ops add up to its busy time) to the program whose run
holds it, under the op's name without XLA's numbering.

Works on the plain event dicts ``trace_reduce.load_xplane`` gives, so the
arithmetic is checked on a small recorded trace with no profiler.
"""

import collections

from perf import trace_reduce

OUTSIDE = "(outside any program)"
#: ``jit_decode(1234567)`` -> ``jit_decode``: the seven prefill buckets are
#: seven programs of one name, and read as one
program_name = trace_reduce.program_name


def by_program(events, chips=1):
    """``{program: {"runs", "module_s", "op_s", "ops": {name: seconds}}}``
    over the ``chips`` first device planes: ``runs`` and ``module_s`` count
    and add up the program's events on the ``XLA Modules`` line (a run's
    duration, first op to last, gaps inside it included); ``op_s`` is the
    self time of the ops its runs hold and ``ops`` the same by op name
    without XLA's numbering. Empty where the trace holds no device plane (a
    CPU run: a test)."""
    planes = sorted({e["plane"] for e in events
                     if trace_reduce.DEVICE_PLANE.match(e["plane"])},
                    key=lambda p: int(
                        trace_reduce.DEVICE_PLANE.match(p).group(1)))[:chips]
    out = collections.defaultdict(lambda: {
        "runs": 0, "module_s": 0.0, "op_s": 0.0,
        "ops": collections.Counter()})
    for plane in planes:
        starts, runs = trace_reduce.module_line(events, plane)
        for _s, _e, name in runs:
            out[name]["runs"] += 1
            out[name]["module_s"] += (_e - _s) * 1e-9
        ops = [e for e in events if e["plane"] == plane
               and e["line"] == trace_reduce.OPS_LINE]
        for e, self_ns in trace_reduce._self_times(ops):
            if self_ns <= 0:
                continue
            book = out[trace_reduce.program_at(starts, runs, e["start_ns"])
                       or OUTSIDE]
            book["op_s"] += self_ns * 1e-9
            book["ops"][trace_reduce.base_name(e["name"])] += self_ns * 1e-9
    return {k: dict(v, ops=dict(v["ops"])) for k, v in out.items()}


def family_seconds(ops, families):
    """Seconds of ``ops`` (name -> seconds) whose name belongs to one of
    ``families``: XLA names a fusion after the ops it was built around,
    joined by ``_`` (``copy_bitcast_fusion``, ``dynamic-slice_fusion``), so
    a name belongs to a family when one of its ``_``-separated parts is the
    family's word or starts with it and a hyphen (``copy-start``)."""
    total = 0.0
    for name, secs in ops.items():
        parts = name.split("_")
        if any(p == f or p.startswith(f + "-") for p in parts
               for f in families):
            total += secs
    return total
