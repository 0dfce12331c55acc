"""Share of the traced window in which no operation ran on the device: 1 -
(union of device-op intervals, averaged over the chips) / traced window."""


def read(ctx):
    r = ctx.reduction
    if not r or ctx.device["platform"] != "tpu" or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
