"""Perf-regression sentinel: a fresh run gated against a baseline run.

A PR that silently halved tokens/s would ship if nothing compared fresh
numbers to a record. ``python -m apex_tpu.monitor.goodput run.jsonl
--check --baseline prior.jsonl`` is that comparison — the same
exit-nonzero discipline as ``python -m apex_tpu.analysis``.

Inputs, both record streams of the same run kind:

- **fresh** — the measurements under test: ``kind="bench"`` records (one
  measurement each, tagged with the platform it ran on), plus
  ``kind="metrics"`` (tokens/s, MFU, step time — medians over the run)
  and ``kind="goodput"`` (goodput fraction) records from a training run.
- **baseline** — a ``--baseline`` recording of a comparable run. Only
  same-(metric, platform) values are compared; a fresh metric with no
  baseline is reported, not failed.

Thresholds are NOISE-AWARE, not bare percentages: the tolerance for a
metric is ``max(floor, 3 * MAD_rel)`` where ``MAD_rel`` is the robust
relative spread of the history's REPEAT measurements (values within
``repeat_band`` of the best — an improving trajectory's early rounds are
progress, not noise, and must not widen the gate). With fewer than two
repeats the floor alone applies. The slope-timing method this protects
is itself noisy at the few-percent level (docs/benchmarking.md), hence
the default 5% floor.

Intentional regressions pass through the same reason-carrying
:class:`~apex_tpu.analysis.findings.Allowlist` as every other gate in
the repo: an entry names the metric and says WHY the slowdown is
accepted (e.g. "traded 3% tokens/s for the verified-checkpoint path");
bare suppressions are a constructor error. Repo entries live in
:data:`GOODPUT_ALLOWLIST` below — currently empty, which is itself the
claim that no regression is being waved through.

jax-free (findings.py is stdlib-only and ``apex_tpu.analysis`` is
PEP-562 lazy): the gate runs on any box.
"""

from statistics import median
from typing import Dict, Iterable, List, Sequence, Tuple

from apex_tpu.analysis.findings import (
    Allowlist,
    Finding,
    SEV_ERROR,
    SEV_INFO,
)

__all__ = [
    "measurements_from_records",
    "noise_tolerance",
    "check_regression",
    "goodput_allowlist",
    "GOODPUT_ALLOWLIST",
]

#: metrics-kind scalar fields the sentinel gates, with direction
#: (True = higher is better)
_METRIC_FIELDS = {"tokens_per_s": True, "mfu": True, "step_ms": False}

def higher_is_better(metric: str) -> bool:
    """Direction of a metric by name: times and memory footprints are
    lower-better, rates and fractions higher-better."""
    if metric in _METRIC_FIELDS:
        return _METRIC_FIELDS[metric]
    if metric.endswith(("_ms", "_s", "_s_per_step", "_seconds")):
        return False
    # memory footprints (the HBM x-ray's peak_hbm_bytes and the serving
    # KV pool's kv_pool_peak_blocks): a regression is the number GROWING
    if metric.endswith(("_bytes", "_blocks")):
        return False
    return True


def measurements_from_records(
    records: Iterable[dict], source: str = "records",
) -> List[dict]:
    """Gateable measurements from a record stream.

    - ``kind="bench"``: one measurement per record (metric/value/
      platform).
    - ``kind="metrics"``: the run's MEDIAN per gated field (one fast
      interval must not mask a slow run, one slow one must not fail it);
      platform tag "run".
    - ``kind="goodput"``: median ``goodput_fraction``; platform "run".
    """
    out: List[dict] = []
    per_field: Dict[str, List[float]] = {}
    goodput_fracs: List[float] = []
    for rec in records:
        kind = rec.get("kind")
        if kind == "bench":
            value = rec.get("value")
            metric = rec.get("metric")
            if isinstance(value, (int, float)) and metric:
                out.append({
                    "metric": str(metric), "value": float(value),
                    "unit": rec.get("unit"),
                    "platform": str(rec.get("platform", "unknown")),
                    "source": source,
                })
        elif kind == "metrics":
            for field in _METRIC_FIELDS:
                v = rec.get(field)
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    per_field.setdefault(field, []).append(float(v))
        elif kind == "goodput":
            v = rec.get("goodput_fraction")
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                goodput_fracs.append(float(v))
    for field, vals in sorted(per_field.items()):
        out.append({
            "metric": field, "value": median(vals), "unit": None,
            "platform": "run", "source": source,
        })
    if goodput_fracs:
        out.append({
            "metric": "goodput_fraction", "value": median(goodput_fracs),
            "unit": None, "platform": "run", "source": source,
        })
    return out


def noise_tolerance(
    history_values: Sequence[float],
    floor: float = 0.05,
    repeat_band: float = 0.15,
    k: float = 3.0,
    higher_better: bool = True,
) -> float:
    """Relative regression tolerance for a metric given its history.

    Repeats = history values within ``repeat_band`` (relative) of the
    best — re-measurements of the same configuration; earlier, worse
    values are trajectory progress and excluded (they would claim the
    improvement itself as "noise" and let a matching regression pass).
    Tolerance = ``max(floor, k * MAD_rel(repeats))``.
    """
    if not history_values:
        return floor
    best = max(history_values) if higher_better else min(history_values)
    if best == 0:
        return floor
    repeats = [v for v in history_values
               if abs(v - best) <= repeat_band * abs(best)]
    if len(repeats) < 2:
        return floor
    med = median(repeats)
    if med == 0:
        return floor
    mad_rel = median(abs(v - med) for v in repeats) / abs(med)
    return max(floor, k * mad_rel)


def _baseline_key(m: dict) -> Tuple[str, str]:
    return (m["metric"], m["platform"])


def check_regression(
    fresh: Sequence[dict],
    history: Sequence[dict],
    floor: float = 0.05,
) -> List[Finding]:
    """Compare fresh measurements to same-(metric, platform) history.

    One finding per fresh measurement: ``perf.regression`` (error) when
    it falls outside the noise-aware band around the historical best,
    ``perf.no-baseline`` (info) when nothing comparable is recorded —
    advisory, because a NEW metric must not fail the gate, but visible,
    because a silently un-gated metric is how trajectories rot.
    """
    by_key: Dict[Tuple[str, str], List[float]] = {}
    for m in history:
        by_key.setdefault(_baseline_key(m), []).append(m["value"])

    findings: List[Finding] = []
    for m in fresh:
        key = _baseline_key(m)
        hist = by_key.get(key)
        site = f"{m['source']}:{m['metric']}"
        if not hist:
            findings.append(Finding(
                rule="perf.no-baseline",
                message=(
                    f"no recorded baseline for metric {m['metric']!r} on "
                    f"platform {m['platform']!r} — value "
                    f"{m['value']:.6g} accepted unchecked"
                ),
                site=site, severity=SEV_INFO,
                data={"metric": m["metric"], "value": m["value"],
                      "platform": m["platform"]},
            ))
            continue
        hib = higher_is_better(m["metric"])
        tol = noise_tolerance(hist, floor=floor, higher_better=hib)
        best = max(hist) if hib else min(hist)
        value = m["value"]
        if hib:
            regressed = value < best * (1.0 - tol)
            change = value / best - 1.0 if best else 0.0
        else:
            regressed = value > best * (1.0 + tol)
            change = best / value - 1.0 if value else 0.0
        if regressed:
            findings.append(Finding(
                rule="perf.regression",
                message=(
                    f"{m['metric']} = {value:.6g} regressed "
                    f"{-100.0 * change:.1f}% vs recorded best {best:.6g} "
                    f"(tolerance {100.0 * tol:.1f}%, platform "
                    f"{m['platform']!r}) — fix it, or allowlist the "
                    f"metric with the reason the slowdown is intentional"
                ),
                site=site, severity=SEV_ERROR,
                data={"metric": m["metric"], "value": value,
                      "baseline": best, "tolerance": tol,
                      "change": change, "platform": m["platform"]},
            ))
    return findings


#: Intentional, documented perf regressions — the reason-carrying
#: mute button, same contract as analysis/allowlist.py. Match is on the
#: finding site (``<source>:<metric>``). EMPTY today: nothing is waived,
#: and any entry added here is a reviewable claim that a specific
#: slowdown buys something worth more.
GOODPUT_ALLOWLIST: List = []


def goodput_allowlist() -> Allowlist:
    """A fresh copy of the perf-regression allowlist (callers may
    :meth:`~apex_tpu.analysis.findings.Allowlist.extended` it)."""
    return Allowlist(list(GOODPUT_ALLOWLIST))
