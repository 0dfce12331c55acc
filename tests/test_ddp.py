"""Behavioral tests for parallel/ddp.py (the reference's
apex.parallel.DistributedDataParallel semantics, parallel/distributed.py:131).

What the reference's 600 lines of bucketed-NCCL machinery ultimately
guarantee is pinned here directly on the 8-device mesh: DP-averaged grads
equal the full-batch gradient, predivide trades fp16 overflow headroom
exactly as documented (distributed.py:439-455), allreduce_always_fp32
accumulates in fp32 and hands back the original dtype, and the init-time
param broadcast makes rank 0 authoritative.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from apex_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel import (
    DistributedDataParallel,
    Reducer,
    all_reduce_gradients,
    broadcast_params,
)

@pytest.fixture
def mesh():
    return Mesh(np.asarray(jax.devices()), ("dp",))


def _loss(params, x, y):
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


class TestAllReduceGradients:
    def test_dp_grads_equal_full_batch_grad(self, mesh, rng):
        """mean over equal shards of per-shard grads == full-batch grad —
        THE data-parallel correctness property."""
        k1, k2, k3 = jax.random.split(rng, 3)
        x = jax.random.normal(k1, (32, 8))
        y = jax.random.normal(k2, (32, 1))
        params = {
            "w": jax.random.normal(k3, (8, 1)),
            "b": jnp.zeros((1,)),
        }
        full = jax.grad(_loss)(params, x, y)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(), P("dp"), P("dp")), out_specs=P(),
        )
        def dp_grads(params, x, y):
            g = jax.grad(_loss)(params, x, y)
            return all_reduce_gradients(g, "dp")

        got = dp_grads(params, x, y)
        for k in full:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(full[k]), rtol=1e-5, atol=1e-6
            )

    def test_predivide_buys_fp16_overflow_headroom(self, mesh):
        """Per-rank VARYING fp16 grads of 30000: a postdivide sum
        overflows fp16 (8 x 30000 >> 65504 -> inf) while
        predivide_factor=8 keeps every partial in range and lands the
        mean — the reference's stated reason for
        gradient_predivide_factor (distributed.py:439-455)."""

        def reduce(factor):
            @jax.jit
            @functools.partial(
                shard_map, mesh=mesh, in_specs=P(), out_specs=P()
            )
            def run(g):
                g = jax.lax.pcast(g, "dp", to="varying")
                return all_reduce_gradients(
                    {"g": g}, "dp", gradient_predivide_factor=factor
                )["g"]

            return run(jnp.float16(30000.0))

        assert not np.isfinite(np.asarray(reduce(1.0)))  # postdivide: inf
        np.testing.assert_allclose(
            np.asarray(reduce(8.0)), 30000.0, rtol=1e-3
        )  # predivide: in-range mean (fp16 sequential-sum rounding)

    def test_allreduce_always_fp32_keeps_dtype_and_value(self, mesh):
        """fp32 accumulation around the psum rescues the same overflow case
        WITHOUT predivide, and the result comes back in the grads' dtype."""

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P()
        )
        def run(g):
            g = jax.lax.pcast(g, "dp", to="varying")
            return all_reduce_gradients(
                {"g": g}, "dp", allreduce_always_fp32=True
            )["g"]

        out = run(jnp.float16(30000.0))
        assert out.dtype == jnp.float16
        np.testing.assert_allclose(np.asarray(out), 30000.0)

    def test_pmean_global_loss_grads_are_final_skip_allreduce(self, mesh):
        """The documented pmean'd-GLOBAL-loss regime (the SyncBatchNorm
        pattern): under checked shard_map those grads arrive unvarying and
        ALREADY AVERAGED — they equal the full-batch gradient with NO call
        to all_reduce_gradients, and calling it anyway silently divides by
        N again (the unvarying type cannot tell a sum from a mean).  Pins
        the docstring's 'skip this function' guidance."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
        x = jax.random.normal(k1, (32, 8))
        y = jax.random.normal(k2, (32, 1))
        params = {
            "w": jax.random.normal(k3, (8, 1)),
            "b": jnp.zeros((1,)),
        }
        full = jax.grad(_loss)(params, x, y)

        def run(call_allreduce):
            @jax.jit
            @functools.partial(
                shard_map, mesh=mesh,
                in_specs=(P(), P("dp"), P("dp")), out_specs=P(),
            )
            def dp_grads(params, x, y):
                g = jax.grad(
                    lambda p: jax.lax.pmean(_loss(p, x, y), "dp")
                )(params)
                return all_reduce_gradients(g, "dp") if call_allreduce else g

            return dp_grads(params, x, y)

        got = run(call_allreduce=False)
        for k in full:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(full[k]), rtol=1e-5, atol=1e-6
            )
        # the trap, pinned so a refactor can't silently change it: the
        # already-reduced branch has no way to know these are means
        wrong = run(call_allreduce=True)
        np.testing.assert_allclose(
            np.asarray(wrong["w"]), np.asarray(full["w"]) / 8.0,
            rtol=1e-5, atol=1e-7,
        )

    def test_sum_mode_when_average_off(self, mesh):
        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")
        )
        def run(g):
            return all_reduce_gradients({"g": g}, "dp", gradient_average=False)["g"]

        g = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
        np.testing.assert_allclose(np.asarray(run(g)), np.full((8, 1), 28.0))


class TestBroadcastAndReducer:
    def test_broadcast_params_makes_rank0_authoritative(self, mesh):
        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")
        )
        def run(p):
            # per-rank distinct params (leading dp dim sliced by shard_map)
            out = broadcast_params({"w": p}, "dp")
            return out["w"]

        p = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) + 5.0
        np.testing.assert_allclose(np.asarray(run(p)), np.full((8, 1), 5.0))

    def test_reducer_means_tree(self, mesh):
        red = Reducer("dp")

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P()
        )
        def run(x):
            return red.reduce({"x": x})["x"]

        x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
        np.testing.assert_allclose(np.asarray(run(x)), [[3.5]])

    def test_reducer_passes_replicated_leaves_through(self, mesh):
        """An already-replicated leaf is its own cross-rank mean — a psum
        would return 8x the value."""
        red = Reducer("dp")

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P()
        )
        def run(x):
            return red.reduce({"x": x})["x"]

        np.testing.assert_allclose(float(run(jnp.float32(5.0))), 5.0)


class TestDistributedDataParallel:
    def test_value_and_grad_returns_synced_grads(self, mesh, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        x = jax.random.normal(k1, (32, 8))
        y = jax.random.normal(k2, (32, 1))
        params = {"w": jax.random.normal(k3, (8, 1)), "b": jnp.zeros((1,))}
        ddp = DistributedDataParallel(loss_fn=_loss)
        full = jax.grad(_loss)(params, x, y)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(), P("dp"), P("dp")), out_specs=(P("dp"), P()),
        )
        def step(params, x, y):
            loss, grads = ddp.value_and_grad()(params, x, y)
            return loss[None], grads

        losses, grads = step(params, x, y)
        assert losses.shape == (8,)
        for k in full:
            np.testing.assert_allclose(
                np.asarray(grads[k]), np.asarray(full[k]), rtol=1e-5,
                atol=1e-6,
            )
