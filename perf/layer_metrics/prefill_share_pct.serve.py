"""Share of the device's busy time in the traced window that ops of the
prefill programs take (every bucket's; ``perf/serve_trace.py`` books each
op's self time to the program whose run holds it). A tick that admits a
request runs its prefill before the decode step, so this share is what
prompts cost the tokens of everyone else."""


def read(ctx):
    programs = ctx.counters.get("programs") or {}
    name = ctx.cell.get("programs", {}).get("prefill")
    total = sum(p["op_s"] for p in programs.values())
    if name not in programs or total <= 0 or programs[name]["op_s"] <= 0:
        return None
    return 100.0 * programs[name]["op_s"] / total
