"""Share of the decode program's device time in ops that only move data:
the self time of the ops inside its runs whose name belongs to one of the
cell's ``copy_families`` (``copy``, ``gather``, ``dynamic-slice``, ``slice``,
``transpose``, also as part of a fusion's name: ``perf/serve_trace.py:
family_seconds``) over the self time of all its ops. The engine gathers each
lane's whole window from the pool, a layer, a token (``serving/engine.py``'s
decode ``lane``); a decode that attends over the pool's blocks in place moves
this towards 0."""


def read(ctx):
    from perf import serve_trace

    name = ctx.cell.get("programs", {}).get("decode")
    p = (ctx.counters.get("programs") or {}).get(name)
    if not p or p["op_s"] <= 0:
        return None
    moved = serve_trace.family_seconds(p["ops"], ctx.cell["copy_families"])
    return moved / p["op_s"] if moved > 0 else None
