"""Slope timing: seconds per iteration of a loop chained on the device.

A per-call wall-clock time includes everything that is not the loop body:
dispatch, the host fetch that forces completion, and whatever sits between
the host and the device. A **slope** removes all of it: run K data-dependent
iterations inside ONE jitted ``lax.fori_loop``/``scan``, force completion
with a small host fetch, and difference two K values so every per-call
constant cancels and only device time per iteration is left.

Two rules keep the measurement honest:

- Benchmark inputs are jit ARGUMENTS, never closed-over arrays: a closure
  is baked into the program as a constant, bloating it and letting XLA fold
  work out of the loop.
- The fetched output must depend on every element of every iteration
  (:func:`full_reduce`), or dead-code elimination narrows the loop to the
  lanes that are read.

Whether a plain host clock around ``block_until_ready`` is already honest
on a given machine is checked by ``chip_smoke.py``'s clock phase (a matmul
chain timed that way must come out at or below the chip's peak);
docs/benchmarking.md records what it found. Nothing here yields a device
number off the device: on a CPU the slope is a CPU time.
"""

import time
from typing import Callable, Sequence

import jax
import numpy as np

__all__ = [
    "fetch",
    "full_reduce",
    "chained_seconds_per_iter",
    "seconds_per_iter",
]


def full_reduce(tree):
    """ONE fp32 scalar depending on every ELEMENT of every leaf.

    This reduction is load-bearing for measurement validity, not a
    convenience: fetching a single element lets XLA trace it back through a
    scan carry and dead-code-eliminate every other lane of an elementwise
    loop body (measured: 0.000 ms Adam "steps"), and one scalar output
    means one host fetch. Use this in every slope-timed ``build`` — do not
    re-implement it inline.
    """
    import jax.numpy as jnp

    return sum(
        jnp.sum(leaf.astype(jnp.float32))
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def fetch(out):
    """Force real device execution by materializing every output leaf on the
    host; returns the numpy leaves.  Outputs must be small (scalars/short
    vectors) — fetching a large array would time the device-to-host
    transfer instead of the computation."""
    return [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(out)]


def _best_of(fn, args, reps):
    out = fetch(fn(*args))  # compile + first run outside the timed region
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fetch(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best, out


def chained_seconds_per_iter(
    build: Callable[[int], Callable],
    args: Sequence,
    reps: int = 5,
    target_signal: float = 0.4,
    max_span: int = 1024,
    return_output: bool = False,
    deadline: float | None = None,
):
    """Seconds per iteration of the loop body that ``build(k)`` chains k times.

    ``build(k)`` must return a function of ``*args`` whose (small) output
    data-depends on all k iterations — typically ``lax.fori_loop``/``scan``
    with the iterate as the carry, reduced via a FULL ``sum`` at the end.
    The result is the slope ``(t(k2) - t(k1)) / (k2 - k1)`` over
    best-of-``reps`` synchronized runs, which cancels every per-call constant
    (dispatch, fetch) and leaves pure device time.

    The span ``k2 - k1`` is sized adaptively: per-call constants jitter, so
    a fixed short span can turn a fast loop into pure noise — even negative
    slopes.  A rough pass estimates the per-iteration time, then the span is
    chosen so the slope signal is ~``target_signal`` seconds, well above
    the jitter.

    Raises ``RuntimeError`` if the final slope comes out non-positive even
    at ``max_span`` — a garbage measurement must never be silently recorded
    as a (nonsensical, huge) throughput.

    With ``return_output=True``, returns ``(seconds, last_output)`` where
    ``last_output`` is the fetched numpy output of the longest chain —
    callers use it as a correctness gate on the exact computation timed.

    ``deadline`` (``time.monotonic()`` value) bounds span escalation: each
    escalation costs one more compile, so past the deadline the next
    escalation raises instead of starting.  An in-flight fetch is never
    interrupted — only the decision to start another one is gated.
    """

    def _check_deadline(where):
        if deadline is not None and time.monotonic() > deadline:
            raise RuntimeError(f"measurement budget exhausted before {where}")

    _check_deadline("first compile")
    t1, _ = _best_of(jax.jit(build(1)), args, reps)
    span = 32
    while True:
        _check_deadline(f"span={span} compile")
        t2, out = _best_of(jax.jit(build(1 + span)), args, reps)
        signal = t2 - t1
        # accept once the slope signal dwarfs the jitter; otherwise escalate
        # the span geometrically (each span is one more compile, so
        # escalate in few, large steps rather than re-estimating precisely)
        if signal >= target_signal or span >= max_span:
            if signal <= 0:
                raise RuntimeError(
                    f"non-positive slope at span={span}: t(1)={t1:.4f}s "
                    f"t({1 + span})={t2:.4f}s — timing is noise, not signal"
                )
            sec = signal / span
            return (sec, out) if return_output else sec
        est = max(signal / span, 1e-6)
        span = min(max_span, max(span * 4, int(target_signal / est) + 1))


def seconds_per_iter(step, carry, xs_like=None, reps: int = 5) -> float:
    """Slope-time one step of ``carry -> carry`` (or ``(carry, x) -> carry``).

    Convenience wrapper for the common benchmark shape: the step function is
    chained via ``lax.scan`` over k dummy iterations with the carry threaded
    through, then reduced to one scalar per carry leaf for the fetch.
    """

    def build(k):
        def run(carry):
            def body(c, _):
                c2 = step(c) if xs_like is None else step(c, xs_like)
                return c2, None

            final, _ = jax.lax.scan(body, carry, None, length=k)
            return full_reduce(final)

        return run

    return chained_seconds_per_iter(build, (carry,), reps=reps)
