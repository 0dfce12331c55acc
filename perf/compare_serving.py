"""The comparison that decides ``correct`` for a served model: the tokens
the timed engine served for a sample of the window's finished requests,
against the plain reference's full forward pass over each prompt and its
served tokens (``perf/reference/gpt_serving.py``). Entries as
``perf/compare.py`` makes them; a run is correct when every value is at or
under its limit (``compare.correct``).

How each limit was set (sound runs' readings below it, the fp8 control's
and the planted faults' above it) is in PERF.md section 2.
"""

import numpy as np

from perf import compare

#: what ``readings`` reports; a cell's file gives a limit to those compared
NUMBERS = ("served_gap_max", "served_gap_p99", "served_gap_mean",
           "served_not_best_share")


def readings(gaps, same):
    """``gaps`` / ``same``: per replayed request, the per-position arrays
    ``reference.gpt_serving.served_gaps`` gives. Over all positions of all
    requests: the widest gap by which a served token's logit lies below the
    reference's best (in logit spreads), the 99th percentile and the mean
    of the gaps, and the share of positions whose token is not the
    reference's best."""
    if not gaps:
        # nothing finished, nothing to hold to the reference: not correct
        return dict.fromkeys(NUMBERS, float("nan"))
    g = np.concatenate([np.asarray(x, np.float64) for x in gaps])
    s = np.concatenate([np.asarray(x, bool) for x in same])
    return {"served_gap_max": float(g.max()),
            "served_gap_p99": float(np.percentile(g, 99)),
            "served_gap_mean": float(g.mean()),
            "served_not_best_share": float(1.0 - s.mean())}


def serving(gaps, same, limits, extra=()):
    """The entries of one run. ``limits`` names the numbers compared (a
    number the cell's file gives no limit is read, not compared);
    ``limits=None`` reports every number against infinity, the studies' way
    of taking readings. ``extra``: (name, value, limit) the driver adds,
    such as the engine's own count of steady-state compiles."""
    r = readings(gaps, same)
    if limits is None:
        limits = dict.fromkeys(NUMBERS, float("inf"))
    out = [compare._entry(n, r[n], limits[n]) for n in NUMBERS
           if limits.get(n) is not None]
    out += [compare._entry(*e) for e in extra]
    return out
