"""Device time of one run of the engine's decode program (all lanes, one
token each): the summed durations of its runs on the trace's ``XLA Modules``
line over their number (``perf/serve_trace.py``, which the driver reads the
trace with; the cell's file names the program). The floor under every gap
between a request's tokens."""


def read(ctx):
    name = ctx.cell.get("programs", {}).get("decode")
    p = (ctx.counters.get("programs") or {}).get(name)
    if not p or not p["runs"]:
        return None
    return 1e3 * p["module_s"] / p["runs"]
