"""How unevenly the router loads the experts held here: the largest number
of rows one held expert took over the mean of the held experts, a layer,
averaged over the expert layers and the window's steps. From the program's
own counter (``moe_load_max_over_mean`` in the step's MetricBag, which the
driver hands on); 1 is perfectly even. A program without the counter gives
nothing to read."""


def read(ctx):
    return ctx.counters.get("moe_load_max_over_mean") or None
