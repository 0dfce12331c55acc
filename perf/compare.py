"""The comparisons that decide ``correct``: what the timed path produced
against what the plain reference gives for the same seed. Each returns a list
of ``{"name", "value", "limit"}``; a run is correct when every value is at or
under its limit. A value that is not a finite number reads as 1e30.

How each limit was set (the readings of sound runs below it, the control's
and the planted faults' above it) is in PERF.md section 2.
"""

import math

import numpy as np

_BROKEN = 1e30


def _entry(name, value, limit, where=None):
    value = float(value)
    if not math.isfinite(value):
        value = _BROKEN
    e = {"name": name, "value": value, "limit": min(float(limit), _BROKEN)}
    if where is not None:
        e["where"] = where
    return e


def _flat(norms):
    """{leaf: scalar or per-layer vector} -> ([labels], vector)."""
    labels, values = [], []
    for name in sorted(norms):
        v = np.atleast_1d(np.asarray(norms[name], np.float64))
        for i, x in enumerate(v):
            labels.append(name if v.size == 1 else f"{name}[{i}]")
            values.append(x)
    return labels, np.asarray(values)


def worst_leaf_gap(got, want, keep=None):
    """The widest gap between the program's norm of a leaf and the
    reference's norm of it (the gap of the norms, not the norm of the
    difference), measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger - some gradients are all but zero.
    ``keep`` masks the leaves that count."""
    labels, w = _flat(want)
    labels_g, g = _flat(got)
    if labels != labels_g:
        raise ValueError("the program's leaves are not the reference's: "
                         f"{sorted(set(labels) ^ set(labels_g))[:6]}")
    gaps = np.abs(g - w) / np.maximum(w, np.median(w))
    gaps = np.where(np.isfinite(gaps), gaps, _BROKEN)
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), labels[i]


def correct(compared):
    """A run's verdict: every number compared is at or under its limit."""
    return all(c["value"] <= c["limit"] for c in compared)


def training(got, want, limits):
    """First steps of a training run. ``got``/``want``: ``losses`` (one per
    step), ``g1`` (per-leaf norm of the first gradient as the optimizer got
    it), ``delta`` (per-leaf norm of the parameters' change over the steps);
    ``got`` also carries ``skipped``, the updates the loss scaler suppressed
    (a skipped update leaves every leaf unmoved: no sound run).

    ``limits`` names the numbers compared. A number the cell's file gives no
    limit (``null`` or absent) is not compared: one for which no control and
    no planted fault reads clear of sound runs could only fail sound runs
    (PERF.md 2 names each with its readings). ``limits=None`` reports every
    number against infinity: the studies' way of taking readings."""
    inf = float("inf")
    if limits is None:
        limits = dict.fromkeys(
            [f"loss_gap_step{i + 1}" for i in range(len(want["losses"]))]
            + ["grad_norm_gap", "update_norm_gap"], inf)
    out = []
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        name = f"loss_gap_step{i + 1}"
        if limits.get(name) is not None:
            out.append(_entry(name, abs(a - b) / abs(b), limits[name]))
    # leaves whose gradient is nought to rounding in the reference move under
    # Adam by round-off alone: left out of the change by a rule on the
    # reference's gradient
    _, g_ref = _flat(want["g1"])
    keep = {"grad": None, "update": g_ref >= 1e-3 * np.median(g_ref)}
    for kind, key in (("grad", "g1"), ("update", "delta")):
        name = f"{kind}_norm_gap"
        if limits.get(name) is not None:
            gap, where = worst_leaf_gap(got[key], want[key], keep[kind])
            out.append(_entry(name, gap, limits[name], where))
    out.append(_entry("skipped_updates", got.get("skipped", 0.0), 0.0))
    return out
