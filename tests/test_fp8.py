"""FP8 delayed-scaling recipe tests.

The reference ships only the amax process groups
(apex/transformer/parallel_state.py:280-292); the recipe pinned here is
the minimal delayed-scaling state machine those groups exist to serve:
real fp8 dtypes, a history window, scale derivation, and amax sync over
the mesh's amax group inside shard_map.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from apex_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.amp.fp8 import (
    FP8_MAX,
    Fp8TensorState,
    dequantize,
    fp8_dense,
    init_fp8_state,
    quantize,
    update_fp8_state,
)
from apex_tpu.parallel import parallel_state


class TestQuantize:
    def test_real_fp8_dtypes(self):
        x = jnp.linspace(-2.0, 2.0, 64)
        q = quantize(x, jnp.float32(1.0), "e4m3")
        assert q.dtype == jnp.float8_e4m3fn
        q5 = quantize(x, jnp.float32(1.0), "e5m2")
        assert q5.dtype == jnp.float8_e5m2

    @pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
    def test_roundtrip_error_bounded(self, rng, fmt):
        """With the scale placing amax at the format max, relative q-error
        is bounded by the format's epsilon (2^-3 e4m3, 2^-2 e5m2)."""
        x = jax.random.normal(rng, (512,))
        amax = jnp.max(jnp.abs(x))
        scale = FP8_MAX[fmt] / amax
        err = np.abs(
            np.asarray(dequantize(quantize(x, scale, fmt), scale) - x)
        )
        eps = 2.0 ** (-3 if fmt == "e4m3" else -2)
        assert (err <= eps * np.abs(np.asarray(x)) + 1e-7).all()

    def test_saturation_not_inf(self):
        """Values beyond the representable range clamp to ±fp8_max instead
        of overflowing to inf/nan (saturating cast)."""
        x = jnp.asarray([1e6, -1e6, 3.0])
        out = np.asarray(dequantize(quantize(x, jnp.float32(1.0), "e4m3"),
                                    jnp.float32(1.0)))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[:2], [448.0, -448.0])


class TestDelayedScaling:
    def test_scale_tracks_window_max(self):
        s = init_fp8_state(history_len=4)
        s = update_fp8_state(s, 2.0, "e4m3")
        np.testing.assert_allclose(float(s.scale), 448.0 / 2.0)
        # a bigger amax takes over immediately
        s = update_fp8_state(s, 8.0, "e4m3")
        np.testing.assert_allclose(float(s.scale), 448.0 / 8.0)
        # ...and persists while it stays inside the window
        for _ in range(3):
            s = update_fp8_state(s, 1.0, "e4m3")
            np.testing.assert_allclose(float(s.scale), 448.0 / 8.0)
        # after history_len more updates the spike ages out
        s = update_fp8_state(s, 1.0, "e4m3")
        np.testing.assert_allclose(float(s.scale), 448.0 / 1.0)

    def test_margin_halves_scale_per_unit(self):
        s = update_fp8_state(init_fp8_state(4), 2.0, "e4m3", margin=1)
        np.testing.assert_allclose(float(s.scale), 448.0 / 2.0 / 2.0)

    def test_zero_window_keeps_scale_one(self):
        s = update_fp8_state(init_fp8_state(4), 0.0, "e4m3")
        np.testing.assert_allclose(float(s.scale), 1.0)


class TestFp8Dense:
    def test_delayed_semantics(self, rng):
        """Step t quantizes with step t-1's statistics: the first call (scale
        1) saturates a large input, the second call — same input — uses the
        amax recorded by the first and recovers accuracy."""
        k1, k2 = jax.random.split(rng)
        x = jax.random.normal(k1, (8, 16)) * 1000.0  # >> 448
        w = jax.random.normal(k2, (16, 4))
        sx, sw = init_fp8_state(4), init_fp8_state(4)
        ref = jnp.dot(x, w)

        y1, (sx, sw) = fp8_dense(x, w, sx, sw)
        err1 = float(jnp.max(jnp.abs(y1 - ref)) / jnp.max(jnp.abs(ref)))
        y2, _ = fp8_dense(x, w, sx, sw)
        err2 = float(jnp.max(jnp.abs(y2 - ref)) / jnp.max(jnp.abs(ref)))
        assert err2 < err1 * 0.2, (err1, err2)
        assert err2 < 0.1

    def test_amax_synced_over_mesh_group(self, rng):
        """Inside shard_map over dp x tp, every rank's returned state must
        carry the GLOBAL amax (pmax over the amax group), not its local
        shard's — the contract of the reference's amax groups."""
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2, pipeline_model_parallel_size=2
        )
        # per-(dp, tp)-shard x: one shard holds the global max
        x = jax.random.normal(rng, (8, 16))
        x = x.at[0, 0].set(37.0)
        w = jax.random.normal(jax.random.fold_in(rng, 1), (16, 4))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(("dp", "tp")), P()),
            out_specs=P(("dp", "tp")),
            check_vma=False,
        )
        def run(x, w):
            sx, sw = init_fp8_state(4), init_fp8_state(4)
            _, (sx, _) = fp8_dense(x, w, sx, sw)
            return sx.amax_history[:1][None]

        amaxes = np.asarray(run(x, w))  # (dp*tp, 1)
        np.testing.assert_allclose(amaxes, 37.0, rtol=1e-6)
