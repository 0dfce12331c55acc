"""In-step metric taps: a jit-compatible bag of named scalar aggregates.

The device cannot afford a host round-trip per metric per step (every
fetch stalls the dispatch pipeline) and the host cannot see inside a
compiled step. :class:`MetricBag` resolves both: the step folds each
scalar into a tiny on-device aggregate (sum / last / max per metric), the
bag rides the step's carried state (donation-friendly: fixed key set, so
the pytree structure never changes between traces), and the host fetches
ONE packed vector per log interval via :func:`read_bag`.

The fetch is deliberately funneled through one code path that counts
itself (:func:`host_fetch_count`) so tests can assert the O(1/interval)
transfer contract instead of trusting a comment.
"""

import threading
from typing import Any, Dict, Mapping, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

#: Aggregation modes. "mean" divides the running sum by the add() count at
#: read time; "sum" reports the raw sum (event counts); "last" keeps the
#: most recent value (gauges like the loss scale); "max" the running max.
MODES = ("mean", "sum", "last", "max")

_fetch_lock = threading.Lock()
_fetches = 0


def host_fetch_count() -> int:
    """Device-to-host fetches performed by :func:`read_bag` this process.

    Test hook for the one-fetch-per-interval contract; monotonic.
    """
    return _fetches


@flax.struct.dataclass
class MetricBag:
    """Named scalar aggregates as a pytree (lives inside jit).

    ``values`` maps metric name -> f32 scalar aggregate and ``counts``
    maps it to the number of FINITE folds it received (non-finite values
    are excluded at :meth:`add` time, so one NaN step cannot poison an
    interval's mean — the anomaly is the sentinel's story, the interval
    mean is the healthy steps' story). ``count`` totals :meth:`add`
    calls. ``spec`` (static aux data, part of the treedef) fixes the key
    set and each metric's mode, so a bag threads through donated jit
    arguments and ``shard_map`` without retracing or structure drift.
    """

    values: Dict[str, jax.Array]
    counts: Dict[str, jax.Array]
    count: jax.Array
    spec: Tuple[Tuple[str, str], ...] = flax.struct.field(pytree_node=False)

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.spec)

    def mode(self, name: str) -> str:
        return dict(self.spec)[name]

    # -- in-step (pure, call under jit) -----------------------------------

    def add(self, **scalars) -> "MetricBag":
        """Fold one step's scalars in; returns the new bag.

        Unknown names raise at trace time (a typo'd metric must not
        vanish silently); omitted names simply don't advance this step.
        Non-finite values are EXCLUDED (the per-metric count does not
        advance): a NaN-poisoned step's loss must not turn the whole
        interval's mean into None — the sentinel's skip/anomaly counters
        carry the anomaly signal instead.
        """
        unknown = set(scalars) - set(self.names)
        if unknown:
            raise KeyError(
                f"metrics {sorted(unknown)} not in bag spec {self.names}"
            )
        modes = dict(self.spec)
        values = dict(self.values)
        counts = dict(self.counts)
        for name, x in scalars.items():
            x = jnp.asarray(x, jnp.float32)
            if x.ndim != 0:
                raise ValueError(
                    f"metric {name!r} must be a scalar, got shape {x.shape}"
                )
            ok = jnp.isfinite(x)
            mode = modes[name]
            if mode in ("mean", "sum"):
                values[name] = self.values[name] + jnp.where(ok, x, 0.0)
            elif mode == "last":
                values[name] = jnp.where(ok, x, self.values[name])
            else:  # max
                values[name] = jnp.maximum(
                    self.values[name], jnp.where(ok, x, -jnp.inf)
                )
            counts[name] = self.counts[name] + jnp.asarray(ok, jnp.int32)
        return self.replace(
            values=values, counts=counts, count=self.count + 1
        )

    def merge(self, other: "MetricBag") -> "MetricBag":
        """Combine two bags with the same spec (e.g. per-phase bags)."""
        if self.spec != other.spec:
            raise ValueError("cannot merge bags with different specs")
        values = {}
        counts = {}
        for name, mode in self.spec:
            a, b = self.values[name], other.values[name]
            if mode in ("mean", "sum"):
                values[name] = a + b
            elif mode == "last":
                # the other bag is the newer one by convention
                values[name] = jnp.where(other.counts[name] > 0, b, a)
            else:
                values[name] = jnp.maximum(a, b)
            counts[name] = self.counts[name] + other.counts[name]
        return self.replace(
            values=values, counts=counts, count=self.count + other.count
        )

    def pack(self) -> jax.Array:
        """Finalized metrics as ONE flat f32 vector (sorted by spec order).

        This is the device end of the single-fetch contract: one small
        array crosses to the host, not len(spec) scalars. A metric with
        zero finite folds packs as NaN (means: 0/0), which reads as None
        downstream rather than a fake 0.
        """
        out = []
        for name, mode in self.spec:
            v = self.values[name]
            c = jnp.asarray(self.counts[name], jnp.float32)
            if mode == "mean":
                out.append(v / c)
            else:
                out.append(jnp.where(c > 0, v, jnp.nan))
        return jnp.stack(out)


def metric_bag(spec: Mapping[str, str]) -> MetricBag:
    """Fresh zeroed bag from ``{name: mode}`` (modes: mean|sum|last|max)."""
    bad = {n: m for n, m in spec.items() if m not in MODES}
    if bad:
        raise ValueError(f"unknown metric modes {bad}; valid: {MODES}")
    frozen = tuple(sorted(spec.items()))
    values, counts = _zero_values(frozen)
    return MetricBag(
        values=values, counts=counts, count=jnp.asarray(0, jnp.int32),
        spec=frozen,
    )


def _zero_values(spec):
    # one asarray call PER leaf: sharing one zero array across leaves
    # aliases their buffers, and a donated bag then trips XLA's
    # "donate the same buffer twice" check (and wedges collectives)
    values = {
        n: jnp.asarray(-jnp.inf if m == "max" else 0.0, jnp.float32)
        for n, m in spec
    }
    counts = {n: jnp.asarray(0, jnp.int32) for n, _ in spec}
    return values, counts


def reset_bag(bag: MetricBag) -> MetricBag:
    """Zeroed bag with ``bag``'s spec (start of the next log interval).

    Pure — usable under jit, or on host to rebuild the carried bag.
    """
    values, counts = _zero_values(bag.spec)
    return bag.replace(
        values=values, counts=counts, count=jnp.asarray(0, jnp.int32)
    )


def read_bag(bag: MetricBag) -> Dict[str, float]:
    """Fetch the bag to host: ``{name: float}`` in ONE device-to-host
    transfer (the packed vector), counted in :func:`host_fetch_count`.

    Metrics whose aggregate is NaN-from-0/0 (never added) come back as
    ``None`` so sinks serialize them honestly.
    """
    global _fetches
    packed = np.asarray(bag.pack())  # the single transfer
    with _fetch_lock:
        _fetches += 1
    out = {}
    for (name, _), v in zip(bag.spec, packed):
        f = float(v)
        out[name] = None if np.isnan(f) or np.isinf(f) else f
    return out


# -- grad-norm taps --------------------------------------------------------


def global_grad_norm(grads: Any) -> jax.Array:
    """Global L2 norm over every leaf: one fused fp32 reduction (the same
    kernel shape as the scaler's overflow check — cheap next to a step)."""
    leaves = jax.tree_util.tree_leaves(grads)
    if not leaves:
        return jnp.asarray(0.0, jnp.float32)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    )


def per_layer_grad_norms(grads: Any, prefix: str = "grad_norm/") -> Dict[str, jax.Array]:
    """L2 norm per TOP-LEVEL entry of a params-like dict (per-layer for the
    transformer stacks, whose params dicts key layers at the top).

    Non-dict pytrees get one ``prefix + 'all'`` entry. Names have '/'
    separators, ready to be bag spec keys.
    """
    if isinstance(grads, Mapping):
        inner = grads.get("params", grads)
        if isinstance(inner, Mapping) and inner:
            return {
                f"{prefix}{k}": global_grad_norm(v) for k, v in inner.items()
            }
    return {prefix + "all": global_grad_norm(grads)}


# -- sow-tap reader --------------------------------------------------------


def taps_from_intermediates(intermediates: Any, reduce: str = "mean") -> Dict[str, jax.Array]:
    """Flatten a flax ``intermediates`` collection into ``{tap_name: scalar}``.

    ``model.apply(..., mutable=["intermediates"])`` returns nested dicts
    whose leaves are tuples of sown arrays (one per ``sow`` call, e.g. one
    per layer). Each leaf is reduced to one f32 scalar (mean over every
    sown array) under the LAST path component — the tap name the layer
    used in ``self.sow("intermediates", name, ...)`` — aggregating all
    layers of a stack into one series, so the metric stream stays O(taps)
    rather than O(taps x layers); per-site detail belongs in profiler
    captures, not the record stream.
    """
    if reduce != "mean":
        raise ValueError("only reduce='mean' is supported")
    out: Dict[str, Any] = {}

    def visit(node):
        if isinstance(node, Mapping):
            for key, sub in node.items():
                if isinstance(sub, Mapping):
                    visit(sub)
                else:
                    vals = sub if isinstance(sub, (tuple, list)) else (sub,)
                    terms = [
                        jnp.mean(jnp.asarray(v, jnp.float32)) for v in vals
                    ]
                    s = sum(terms) / len(terms)
                    out.setdefault(key, []).append(s)

    visit(intermediates)
    return {
        name: sum(parts) / len(parts) for name, parts in out.items()
    }
