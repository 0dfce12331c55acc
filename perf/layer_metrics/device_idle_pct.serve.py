"""Share of the traced window (the last seconds of arrivals, before the
drain) in which no operation ran on the device: 1 - (union of device-op
intervals, averaged over the chips) / traced window. What the host does in
the gaps is in the line's ``breakdown.idle_gaps``, by the driver's spans
(``submit``, ``tick``, ``observe``, ``wait``)."""


def read(ctx):
    r = ctx.reduction
    if not r or ctx.device["platform"] != "tpu" or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
