"""Tests for apex_tpu.utils.benchmarking (the slope timer).

Timing itself can't be asserted tightly in CI; these pin the harness
mechanics — chains really run k times, outputs are returned, and the
escalation loop terminates.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.utils.benchmarking import (
    chained_seconds_per_iter,
    fetch,
    seconds_per_iter,
)


def test_fetch_returns_numpy_leaves():
    out = fetch({"a": jnp.ones(3), "b": (jnp.zeros(()),)})
    assert len(out) == 2
    assert all(isinstance(x, np.ndarray) for x in out)


def test_chained_runs_k_iterations_and_returns_output():
    calls = []

    # the body must cost ~ms, not ~ns: a trivial body's slope is below
    # timer noise and correctly trips the non-positive-slope raise
    def build(k):
        calls.append(k)

        def run(x):
            def body(c, _):
                return jnp.tanh(c @ c + 0.1), None  # bounded: no overflow

            c, _ = jax.lax.scan(body, x, None, length=k)
            return c[0, 0]

        return run

    x = jnp.eye(256, dtype=jnp.float32)
    sec, out = chained_seconds_per_iter(
        build, (x,), reps=1, target_signal=0.0, return_output=True,
    )
    assert sec > 0.0
    # first span is 32: [1, 33] and acceptance at the 0.0 target
    assert calls == [1, 33]
    assert np.isfinite(out[0])


def test_chained_escalates_span_until_signal():
    spans = []

    def build(k):
        spans.append(k)

        def run(x):
            def body(c, _):
                return jnp.sin(c), None

            c, _ = jax.lax.scan(body, x, None, length=k)
            return c

        return run

    # unreachable signal target forces escalation to max_span exactly once
    try:
        chained_seconds_per_iter(
            build, (jnp.float32(1.0),), reps=1, target_signal=1e9,
            max_span=128,
        )
    except RuntimeError:
        pass  # slope may be ~0 for this trivial body; the raise is correct
    assert spans[0] == 1 and spans[1] == 33 and spans[-1] == 129


def test_seconds_per_iter_threads_carry():
    a = jnp.eye(256, dtype=jnp.float32) * 0.5
    sec = seconds_per_iter(lambda c: c @ a + 1.0, a, reps=1)
    assert sec > 0.0


def test_nonpositive_slope_raises_instead_of_recording_garbage(monkeypatch):
    import apex_tpu.utils.benchmarking as B

    times = iter([5.0, 5.0])  # t(1) == t(1+span): zero slope at max_span

    def fake_best_of(fn, args, reps):
        return next(times), [np.float32(0.0)]

    monkeypatch.setattr(B, "_best_of", fake_best_of)
    with pytest.raises(RuntimeError, match="non-positive slope"):
        B.chained_seconds_per_iter(
            lambda k: lambda: None, (), target_signal=1e9, max_span=32
        )
