"""ZeRO optimizer parity tests: sharded state must reproduce the dense
optimizers exactly (ref: contrib DistributedFusedAdam/LAMB are validated
against their dense counterparts in apex/contrib/test/optimizers)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from apex_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.optimizers import (
    distributed_fused_adam,
    distributed_fused_lamb,
    fused_adam,
    fused_lamb,
)
from apex_tpu.parallel import parallel_state

DP = 4


def make_params(rng):
    # uneven leaf sizes exercise padding + segment boundaries
    return {
        "a": {"kernel": jax.random.normal(rng, (5, 3)), "bias": jnp.ones((3,))},
        "b": {"kernel": jax.random.normal(jax.random.fold_in(rng, 1), (7,))},
    }


# NOTE: grads enter replicated (in_specs=P()), so psum_scatter sums DP
# copies; average_grads=True divides by DP making the scattered grads
# EXACTLY the dense grads — the parity below is exact, not scale-invariant.
def run_distributed(opt_factory, params, grads_seq):
    mesh = parallel_state.initialize_model_parallel(devices=jax.devices()[:DP])
    opt = opt_factory()

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=False,
    )
    def steps(params, grads_seq):
        state = opt.init(params)

        def body(carry, g):
            p, s = carry
            updates, s = opt.update(g, s, p)
            return (optax.apply_updates(p, updates), s), None

        (p, _), _ = jax.lax.scan(body, (params, state), grads_seq)
        return p

    return steps(params, grads_seq)


def run_dense(opt, params, grads_seq):
    state = opt.init(params)
    for i in range(jax.tree_util.tree_leaves(grads_seq)[0].shape[0]):
        g = jax.tree_util.tree_map(lambda a: a[i], grads_seq)
        updates, state = opt.update(g, state, params)
        params = optax.apply_updates(params, updates)
    return params


@pytest.fixture
def grads_seq(rng):
    params = make_params(rng)
    return jax.tree_util.tree_map(
        lambda p: jax.random.normal(
            jax.random.fold_in(rng, p.size), (4,) + p.shape
        ),
        params,
    )


class TestDistributedFusedAdam:
    def test_matches_dense_adam(self, rng, grads_seq):
        params = make_params(rng)
        got = run_distributed(
            lambda: distributed_fused_adam(
                lr=1e-2, weight_decay=0.01, axis_size=DP, average_grads=True
            ),
            params,
            grads_seq,
        )
        want = run_dense(fused_adam(lr=1e-2, weight_decay=0.01), params, grads_seq)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            got,
            want,
        )

    def test_sharded_grad_clip_matches_dense_preclip(self, rng, grads_seq):
        """max_grad_norm clips the GLOBAL norm computed shard-locally +
        psum — must equal dense Adam on grads pre-clipped with the torch
        convention min(1, max/(norm+1e-6)) (ref contrib DFA grad clip)."""
        params = make_params(rng)
        max_norm = 0.5
        got = run_distributed(
            lambda: distributed_fused_adam(
                lr=1e-2, axis_size=DP, average_grads=True,
                max_grad_norm=max_norm,
            ),
            params,
            grads_seq,
        )

        def preclip(g):
            norm = jnp.sqrt(sum(
                jnp.sum(jnp.square(l)) for l in jax.tree_util.tree_leaves(g)
            ))
            c = jnp.minimum(1.0, max_norm / (norm + 1e-6))
            return jax.tree_util.tree_map(lambda l: l * c, g)

        clipped_seq = [
            preclip(jax.tree_util.tree_map(lambda a: a[i], grads_seq))
            for i in range(4)
        ]
        clipped_seq = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *clipped_seq
        )
        want = run_dense(fused_adam(lr=1e-2), params, clipped_seq)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            got,
            want,
        )

    def test_store_param_remainders_matches_fp32_master(self, rng, grads_seq):
        """bf16 params + uint16 remainder shard carry the SAME fp32 master
        trajectory as the fp32-master mode, with half the shard memory:
        master = (param high bits | remainder low bits) exactly.  Params
        differ from the fp32 mode only in the fp32->bf16 convention
        (truncation to the high half vs round-to-nearest), i.e. by at most
        one bf16 ulp (ref store_param_remainders semantics)."""
        import dataclasses

        from apex_tpu.ops.multi_tensor import flatten_pytree
        from apex_tpu.optimizers import zero_state_specs

        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), make_params(rng)
        )
        mesh = parallel_state.initialize_model_parallel(
            devices=jax.devices()[:DP]
        )
        sspec = zero_state_specs("dp")

        def run(remainders):
            opt = distributed_fused_adam(
                lr=1e-2, weight_decay=0.01, axis_size=DP,
                average_grads=True, store_param_remainders=remainders,
            )

            @jax.jit
            @functools.partial(
                shard_map, mesh=mesh, in_specs=(P(), P()),
                out_specs=(P(), sspec), check_vma=False,
            )
            def steps(params, gseq):
                state = opt.init(params)

                def body(carry, g):
                    p, s = carry
                    updates, s = opt.update(g, s, p)
                    return (optax.apply_updates(p, updates), s), None

                (p, s), _ = jax.lax.scan(body, (params, state), gseq)
                return p, s

            return steps(params, grads_seq)

        p_rem, s_rem = run(True)
        p_f32, s_f32 = run(False)

        # reconstruct the remainder mode's master: param high bits | lo
        flat, _ = flatten_pytree(p_rem, dtype=jnp.bfloat16)
        pad = s_rem.master_shard.shape[0] - flat.shape[0]
        flat = jnp.pad(flat, (0, pad))
        hi = jax.lax.bitcast_convert_type(flat, jnp.uint16).astype(jnp.uint32)
        recon = jax.lax.bitcast_convert_type(
            (hi << 16) | s_rem.master_shard.astype(jnp.uint32), jnp.float32
        )
        np.testing.assert_array_equal(
            np.asarray(recon), np.asarray(s_f32.master_shard)
        )
        # params agree to one bf16 ulp (truncation vs nearest)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2**-7,
            ),
            p_rem,
            p_f32,
        )

    def test_remainder_mode_rejects_fp32_params(self, rng):
        params = make_params(rng)
        mesh = parallel_state.initialize_model_parallel(
            devices=jax.devices()[:DP]
        )
        opt = distributed_fused_adam(axis_size=DP, store_param_remainders=True)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def init(params):
            opt.init(params)
            return jnp.zeros(())

        with pytest.raises(ValueError, match="bfloat16"):
            init(params)

    def test_sharded_state_checkpoint_resume(self, rng, grads_seq, tmp_path):
        """the ZeRO state crosses the shard_map boundary
        with zero_state_specs (per-rank shards concatenated into global
        flat arrays), round-trips through utils.checkpoint, and a resumed
        run continues the param trace exactly where the straight run is
        after the same number of steps."""
        from apex_tpu.optimizers import zero_state_specs
        from apex_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

        params = make_params(rng)
        mesh = parallel_state.initialize_model_parallel(
            devices=jax.devices()[:DP]
        )
        opt = distributed_fused_adam(
            lr=1e-2, weight_decay=0.01, axis_size=DP, average_grads=True,
            max_grad_norm=1.0,
        )
        sspec = zero_state_specs("dp")

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=sspec,
            check_vma=False,
        )
        def init(params):
            return opt.init(params)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), sspec, P()),
            out_specs=(P(), sspec), check_vma=False,
        )
        def steps(params, state, gseq):
            def body(carry, g):
                p, s = carry
                updates, s = opt.update(g, s, p)
                return (optax.apply_updates(p, updates), s), None

            (p, s), _ = jax.lax.scan(body, (params, state), gseq)
            return p, s

        first2 = jax.tree_util.tree_map(lambda a: a[:2], grads_seq)
        last2 = jax.tree_util.tree_map(lambda a: a[2:], grads_seq)

        # straight: 4 steps
        state = init(params)
        p_all, _ = steps(params, state, grads_seq)

        # interrupted: 2 steps, checkpoint, restore, 2 more steps
        state = init(params)
        p_mid, s_mid = steps(params, state, first2)
        save_checkpoint(str(tmp_path), 2, {"params": p_mid, "opt": s_mid})
        restored = load_checkpoint(
            str(tmp_path), target={"params": p_mid, "opt": s_mid}
        )
        p_res, _ = steps(restored["params"], restored["opt"], last2)

        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            p_res,
            p_all,
        )


class TestDistributedFusedLAMB:
    @pytest.mark.parametrize("use_nvlamb", [False, True])
    def test_matches_dense_lamb(self, rng, grads_seq, use_nvlamb):
        params = make_params(rng)
        got = run_distributed(
            lambda: distributed_fused_lamb(
                lr=1e-2, weight_decay=0.01, max_grad_norm=1.0,
                use_nvlamb=use_nvlamb, axis_size=DP, average_grads=True,
            ),
            params,
            grads_seq,
        )
        want = run_dense(
            fused_lamb(
                lr=1e-2, weight_decay=0.01, max_grad_norm=1.0,
                use_nvlamb=use_nvlamb,
            ),
            params,
            grads_seq,
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
            got,
            want,
        )

    def test_state_is_sharded(self, rng):
        """ZeRO property: per-device optimizer state is 1/DP of the padded
        total."""
        params = make_params(rng)
        mesh = parallel_state.initialize_model_parallel(devices=jax.devices()[:DP])
        opt = distributed_fused_lamb(axis_size=DP)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def init(params):
            s = opt.init(params)
            return jnp.asarray(s.master_shard.shape[0])

        from apex_tpu.ops.multi_tensor import flatten_pytree

        total = sum(p.size for p in jax.tree_util.tree_leaves(params))
        padded = flatten_pytree(params)[1].padded_total
        # padding rounds tiny trees up to CHUNK_SIZE; the ZeRO property is
        # shard = padded/DP per device
        shard = int(init(params))
        assert shard * DP >= total
        assert shard <= max(padded, total) // DP


class TestZeROInPipelineTopology:
    def test_zero_dp_inside_pp_mesh_trains(self, rng):
        """ZeRO-2 over the dp axis while pp>1 partitions the model: each
        pp rank keeps its own stage params, the optimizer state is 1/dp
        per device WITHIN each stage, and two training steps through the
        compiled pipeline schedule decrease the loss. (The dense-parity
        tests pin the math on a pure-dp mesh; this pins the topology the
        reference's DistributedFusedAdam actually runs in.)"""
        import jax.numpy as jnp

        from apex_tpu.models.gpt_pipeline import build_gpt_pipeline
        from apex_tpu.parallel.pipeline import forward_backward_with_pre_post
        from apex_tpu.transformer import TransformerConfig

        pp, dp = 2, 4
        mesh = parallel_state.initialize_model_parallel(
            pipeline_model_parallel_size=pp,
            devices=jax.devices()[: pp * dp],
        )
        vocab, seq, mb, num_micro = 32, 8, 2, 2
        cfg = TransformerConfig(
            num_layers=2 * pp,
            hidden_size=16,
            num_attention_heads=4,
            vocab_size=vocab,
            max_position_embeddings=seq,
            hidden_dropout=0.0,
            attention_dropout=0.0,
            compute_dtype=jnp.float32,
        )
        parts = build_gpt_pipeline(cfg, pp)
        opt = distributed_fused_adam(
            lr=5e-3, axis_size=dp, average_grads=True, max_grad_norm=1.0
        )
        key = jax.random.PRNGKey(0)
        n_steps = 4
        tokens = jax.random.randint(
            key, (n_steps, num_micro, mb * dp, seq), 0, vocab
        )
        labels = jnp.roll(tokens, -1, axis=3)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(None, None, "dp"), P(None, None, "dp")),
            out_specs=(P(), P()),
            check_vma=False,
        )
        def train(tokens, labels):
            init_key = jax.random.PRNGKey(0)
            pre = parts.embed.init(init_key, tokens[0, 0])["params"]
            h0 = parts.pre_fn(pre, tokens[0, 0])
            r = jax.lax.axis_index("pp")
            stage = parts.chunk.init(
                jax.random.fold_in(jax.random.fold_in(init_key, 7), r), h0
            )["params"]
            params = {
                "pre": pre,
                "stages": stage,
                "post": parts.init_post(jax.random.fold_in(init_key, 9)),
            }
            state = opt.init(params)

            def one_step(carry, batch):
                params, state = carry
                step_tokens, step_labels = batch
                loss, _, grads = forward_backward_with_pre_post(
                    parts.pre_fn, parts.stage_fn, parts.post_loss_fn,
                    params, step_tokens, step_labels, axis_name="pp",
                )
                # ZeRO's psum_scatter over dp IS the gradient sync
                updates, state = opt.update(grads, state, params)
                params = optax.apply_updates(params, updates)
                return (params, state), jax.lax.pmean(
                    jax.lax.pmean(loss, "dp"), "pp"
                )

            (params, state), losses = jax.lax.scan(
                one_step, (params, state), (tokens, labels)
            )
            return losses, jnp.asarray(state.master_shard.shape[0])

        losses, shard = train(tokens, labels)
        losses = np.asarray(losses)
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0], losses
        # ZeRO property inside the pp mesh: a real (nonzero) per-device
        # shard exists and dp of them cover this rank's padded params
        assert int(shard) > 0


class TestParamGatherPrefetch:
    """The double-buffered param all-gather prefetch: every depth must
    be bitwise-identical to the whole-shard gather (the bucketing is a
    schedule change, not a numerics change), the depth rule must follow
    the ICI roofline, and the bucketed gathers must stay ledger-exact."""

    @pytest.mark.parametrize("factory", [
        distributed_fused_adam, distributed_fused_lamb,
    ])
    @pytest.mark.parametrize("buckets", [2, 3, None])
    def test_bitwise_matches_single_gather(self, rng, grads_seq, factory,
                                           buckets):
        params = make_params(rng)
        base = run_distributed(
            lambda: factory(lr=1e-2, weight_decay=0.01, axis_size=DP,
                            average_grads=True, param_gather_buckets=1),
            params, grads_seq,
        )
        got = run_distributed(
            lambda: factory(lr=1e-2, weight_decay=0.01, axis_size=DP,
                            average_grads=True,
                            param_gather_buckets=buckets),
            params, grads_seq,
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            base, got,
        )

    def test_remainder_mode_bitwise_across_depths(self, rng, grads_seq):
        """store_param_remainders buckets the bf16-high gather + uint16
        state the same way — bitwise at every depth."""
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), make_params(rng)
        )
        mesh = parallel_state.initialize_model_parallel(
            devices=jax.devices()[:DP]
        )

        def run(buckets):
            opt = distributed_fused_adam(
                lr=1e-2, axis_size=DP, average_grads=True,
                store_param_remainders=True, param_gather_buckets=buckets,
            )

            @jax.jit
            @functools.partial(
                shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                check_vma=False,
            )
            def steps(params, gseq):
                state = opt.init(params)

                def body(carry, g):
                    p, s = carry
                    updates, s = opt.update(g, s, p)
                    return (optax.apply_updates(p, updates), s), None

                (p, _), _ = jax.lax.scan(body, (params, state), gseq)
                return p

            return steps(params, grads_seq)

        base = run(1)
        got = run(3)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32)
            ),
            base, got,
        )

    def test_choose_overlap_buckets_roofline_rule(self):
        from apex_tpu.optimizers import choose_overlap_buckets

        # size-1 axis: no gather at all
        assert choose_overlap_buckets(10 * 2**20, 1) == 1
        # unknown bandwidth: plain double-buffering, never a fake roofline
        assert choose_overlap_buckets(10 * 2**20, 8, bandwidth=None) == 2
        # v5e (200 GB/s): a 40 MiB shard over 8 ranks gathers
        # 7*40 MiB ~= 1.47 ms -> 3 buckets of ~0.5 ms each
        assert choose_overlap_buckets(40 * 2**20, 8, bandwidth=200e9) == 3
        # tiny shard: the gather is below one quantum, nothing to hide
        assert choose_overlap_buckets(1024, 8, bandwidth=200e9) == 1
        # huge shard: clamped to the max depth
        assert choose_overlap_buckets(2**31, 8, bandwidth=200e9) == 8
        # depth grows monotonically with bytes
        depths = [
            choose_overlap_buckets(nbytes, 8, bandwidth=200e9)
            for nbytes in (2**18, 2**22, 2**26, 2**30)
        ]
        assert depths == sorted(depths)

    def test_prefetch_ledger_bytes_exact(self, rng):
        """The bucketed gathers stay ledger-routed with exact bytes: nb
        all_gather entries whose payloads sum to the (bucket-padded)
        shard — predicted == what the compiled program ships."""
        from apex_tpu.monitor.xray import ledger as xlax
        from apex_tpu.optimizers import zero_state_specs

        params = make_params(rng)
        mesh = parallel_state.initialize_model_parallel(
            devices=jax.devices()[:DP]
        )
        nb = 3
        opt = distributed_fused_adam(
            lr=1e-2, axis_size=DP, average_grads=True,
            param_gather_buckets=nb,
        )
        sspec = zero_state_specs("dp")

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=sspec,
            check_vma=False,
        )
        def init(params):
            return opt.init(params)

        state = jax.eval_shape(init, params)
        shard = state.master_shard.shape[0] // DP
        bs = -(-shard // nb)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), sspec), out_specs=P(),
            check_vma=False,
        )
        def one_update(params, state):
            g = jax.tree_util.tree_map(jnp.ones_like, params)
            updates, _ = opt.update(g, state, params)
            return updates

        led = xlax.predict_comms(one_update, params, state)
        gathers = led.filter(op="all_gather", axis="dp")
        assert len(gathers) == nb
        assert all(e.shape == (bs,) for e in gathers)
        # total gathered elements == the bucket-padded shard, and the
        # per-chip wire bytes follow the ring all_gather convention
        assert sum(e.shape[0] for e in gathers) == bs * nb
        assert all(e.ici_bytes == (DP - 1) * bs * 4 for e in gathers)


class TestCheckedShardMapGrads:
    """Under jax's CHECKED shard_map (check_vma=True, the default),
    jax.grad w.r.t. dp-replicated params already returns the cross-rank
    SUM (auto-psum in the transpose). zero_scatter_grads must not psum
    again — with average_grads=True the scattered shard must be exactly
    the full-batch MEAN gradient slice. Scale-sensitive on the raw
    shards (Adam's m/sqrt(v) ratio is scale-invariant and would mask a
    uniform factor-of-N error)."""

    def test_scatter_of_autosummed_grads_is_exact_mean(self, rng):
        from apex_tpu.optimizers.distributed_fused_adam import (
            _padded_flatten,
            zero_scatter_grads,
        )

        mesh = parallel_state.initialize_model_parallel(
            devices=jax.devices()[:DP]
        )
        params = make_params(rng)
        x = jax.random.normal(jax.random.fold_in(rng, 5), (32, 5))

        def loss(p, x):
            h = x @ p["a"]["kernel"] + p["a"]["bias"]  # (n, 3)
            # touch every leaf incl. the unrelated-size b.kernel
            return jnp.mean(h ** 2) + jnp.sum(p["b"]["kernel"] ** 2)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P("dp")
        )
        def scattered(p, x):
            g = jax.grad(loss)(p, x)
            shard, _ = zero_scatter_grads(g, "dp", DP, True)
            return shard[None]

        got = np.asarray(scattered(params, x)).reshape(-1)
        want_flat, _ = _padded_flatten(
            jax.grad(loss)(params, x), DP
        )  # full-batch mean-loss grads, the DDP ground truth
        np.testing.assert_allclose(got, np.asarray(want_flat),
                                   rtol=1e-5, atol=1e-6)

    def test_pmean_global_loss_grads_with_average_off(self, rng):
        """The SyncBatchNorm doc pattern: jax.grad of a pmean'd GLOBAL
        loss returns the MEAN already — average_grads=False must slice it
        through unchanged (the documented contract)."""
        from apex_tpu.optimizers.distributed_fused_adam import (
            _padded_flatten,
            zero_scatter_grads,
        )

        mesh = parallel_state.initialize_model_parallel(
            devices=jax.devices()[:DP]
        )
        params = make_params(rng)
        x = jax.random.normal(jax.random.fold_in(rng, 5), (32, 5))

        def local_loss(p, x):
            h = x @ p["a"]["kernel"] + p["a"]["bias"]
            return jnp.mean(h ** 2) + jnp.sum(p["b"]["kernel"] ** 2)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P("dp")
        )
        def scattered(p, x):
            g = jax.grad(
                lambda p: jax.lax.pmean(local_loss(p, x), "dp")
            )(p)
            shard, _ = zero_scatter_grads(g, "dp", DP, average=False)
            return shard[None]

        got = np.asarray(scattered(params, x)).reshape(-1)
        want_flat, _ = _padded_flatten(
            jax.grad(lambda p: local_loss(p, x))(params, ), DP
        )
        np.testing.assert_allclose(got, np.asarray(want_flat),
                                   rtol=1e-5, atol=1e-6)

    def test_mixed_vma_tree_per_leaf_dispatch(self, rng):
        """One varying leaf must not drag already-summed leaves through a
        second psum (concatenate auto-pvarys mixed operands): each leaf
        lands as the exact mean regardless of its regime."""
        from apex_tpu.optimizers.distributed_fused_adam import (
            _padded_flatten,
            zero_scatter_grads,
        )

        mesh = parallel_state.initialize_model_parallel(
            devices=jax.devices()[:DP]
        )
        params = make_params(rng)
        x = jax.random.normal(jax.random.fold_in(rng, 5), (32, 5))

        def local_loss(p, x):
            h = x @ p["a"]["kernel"] + p["a"]["bias"]
            return jnp.mean(h ** 2)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P("dp")
        )
        def scattered(p, x):
            g = jax.grad(lambda p: local_loss(p, x))(p)  # auto-summed, b=0
            # replace the b leaf with a hand-built VARYING per-rank grad
            # whose mean is exactly ones
            g["b"]["kernel"] = jax.lax.pcast(
                jnp.ones_like(p["b"]["kernel"]), "dp", to="varying"
            )
            shard, _ = zero_scatter_grads(g, "dp", DP, average=True)
            return shard[None]

        got = np.asarray(scattered(params, x)).reshape(-1)
        want = jax.grad(lambda p: local_loss(p, x))(params)
        want["b"]["kernel"] = jnp.ones_like(params["b"]["kernel"])
        want_flat, _ = _padded_flatten(want, DP)
        np.testing.assert_allclose(got, np.asarray(want_flat),
                                   rtol=1e-5, atol=1e-6)
