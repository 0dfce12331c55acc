"""Tensor/sequence-parallel tests on the virtual 8-device mesh.

Mirrors the reference's distributed-in-process tier (tests/L0/run_transformer/
test_layers.py, test_mapping.py, test_cross_entropy.py) — here shard_map over
the 'tp' axis of a real Mesh replaces MultiProcessTestCase, and parity is
checked against single-device dense compositions with identical weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P
from apex_tpu.compat import shard_map

from apex_tpu.models import GPTModel, gpt_loss_fn
from apex_tpu.parallel import parallel_state
from apex_tpu.parallel.random import checkpoint_distributed
from apex_tpu.parallel.cross_entropy import vocab_parallel_cross_entropy
from apex_tpu.parallel.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from apex_tpu.transformer import TransformerConfig

TP = 8
VOCAB = 64


def tp_mesh():
    return parallel_state.initialize_model_parallel(tensor_model_parallel_size=TP)


def tiny_cfg(**kw):
    defaults = dict(
        num_layers=2,
        hidden_size=32,
        num_attention_heads=8,
        vocab_size=VOCAB,
        max_position_embeddings=32,
        hidden_dropout=0.0,
        attention_dropout=0.0,
        compute_dtype=jnp.float32,
    )
    defaults.update(kw)
    return TransformerConfig(**defaults)


class TestTPLinears:
    def test_column_parallel_matches_dense(self, rng):
        mesh = tp_mesh()
        x = jax.random.normal(rng, (4, 16), jnp.float32)
        kernel = jax.random.normal(jax.random.fold_in(rng, 1), (16, 24))
        bias = jax.random.normal(jax.random.fold_in(rng, 2), (24,))
        mod = ColumnParallelLinear(output_size=24, gather_output=True)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(None, "tp"), P("tp")),
            out_specs=P(),
            check_vma=False,
        )
        def run(x, k_local, b_local):
            return mod.apply({"params": {"kernel": k_local, "bias": b_local}}, x)

        np.testing.assert_allclose(
            run(x, kernel, bias), x @ kernel + bias, rtol=1e-5, atol=1e-5
        )

    def test_row_parallel_matches_dense(self, rng):
        mesh = tp_mesh()
        x = jax.random.normal(rng, (4, 16), jnp.float32)
        kernel = jax.random.normal(jax.random.fold_in(rng, 1), (16, 24))
        bias = jax.random.normal(jax.random.fold_in(rng, 2), (24,))
        mod = RowParallelLinear(output_size=24, input_is_parallel=False)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P("tp", None), P()),
            out_specs=P(),
            check_vma=False,
        )
        def run(x, k_local, b):
            return mod.apply({"params": {"kernel": k_local, "bias": b}}, x)

        np.testing.assert_allclose(
            run(x, kernel, bias), x @ kernel + bias, rtol=1e-5, atol=1e-5
        )

    def test_vocab_parallel_embedding_matches_dense(self, rng):
        mesh = tp_mesh()
        table = jax.random.normal(rng, (VOCAB, 8))
        ids = jax.random.randint(jax.random.fold_in(rng, 1), (4, 6), 0, VOCAB)
        mod = VocabParallelEmbedding(num_embeddings=VOCAB, embedding_dim=8)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P("tp", None), P()),
            out_specs=P(),
            check_vma=False,
        )
        def run(t_local, ids):
            return mod.apply({"params": {"embedding": t_local}}, ids)

        np.testing.assert_allclose(run(table, ids), table[ids], rtol=1e-6, atol=1e-6)

    def test_vocab_parallel_cross_entropy(self, rng):
        mesh = tp_mesh()
        logits = jax.random.normal(rng, (4, 6, VOCAB))
        target = jax.random.randint(jax.random.fold_in(rng, 1), (4, 6), 0, VOCAB)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(None, None, "tp"), P()),
            out_specs=P(),
            check_vma=False,
        )
        def run(logits_local, target):
            return vocab_parallel_cross_entropy(logits_local, target)

        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ref = lse - jnp.take_along_axis(logits, target[..., None], -1)[..., 0]
        np.testing.assert_allclose(run(logits, target), ref, rtol=1e-5, atol=1e-5)

    def test_column_row_grads_match_dense(self, rng):
        """d/dx and d/dW of Column→gelu→Row == dense MLP grads."""
        mesh = tp_mesh()
        x = jax.random.normal(rng, (4, 16))
        k1 = jax.random.normal(jax.random.fold_in(rng, 1), (16, 32)) * 0.1
        k2 = jax.random.normal(jax.random.fold_in(rng, 2), (32, 16)) * 0.1
        col = ColumnParallelLinear(output_size=32, use_bias=False)
        row = RowParallelLinear(output_size=16, use_bias=False)

        def dense_loss(x, k1, k2):
            return jnp.sum(jax.nn.gelu(x @ k1, approximate=True) @ k2)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(None, "tp"), P("tp", None)),
            out_specs=(P(), P(None, "tp"), P("tp", None)),
            check_vma=False,
        )
        def tp_grads(x, k1l, k2l):
            def loss(x, k1l, k2l):
                h = col.apply({"params": {"kernel": k1l}}, x)
                h = jax.nn.gelu(h, approximate=True)
                y = row.apply({"params": {"kernel": k2l}}, h)
                return jnp.sum(y)

            return jax.grad(loss, argnums=(0, 1, 2))(x, k1l, k2l)

        gx, gk1, gk2 = tp_grads(x, k1, k2)
        rx, rk1, rk2 = jax.grad(dense_loss, argnums=(0, 1, 2))(x, k1, k2)
        np.testing.assert_allclose(gx, rx, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(gk1, rk1, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(gk2, rk2, rtol=1e-4, atol=1e-5)


class TestGPTTensorParallel:
    def _train_losses(self, cfg, rng, steps=10):
        mesh = tp_mesh()
        tokens = jax.random.randint(rng, (4, 16), 0, VOCAB)
        labels = jnp.roll(tokens, -1, axis=1)
        model = GPTModel(config=cfg)
        opt = optax.adam(1e-3)

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        def train(tokens, labels):
            params = model.init(jax.random.PRNGKey(0), tokens)
            opt_state = opt.init(params)

            def step(carry, _):
                params, opt_state = carry

                def loss_fn(p):
                    losses = model.apply(p, tokens, labels=labels)
                    return gpt_loss_fn(losses)

                loss, grads = jax.value_and_grad(loss_fn)(params)
                updates, opt_state = opt.update(grads, opt_state)
                return (optax.apply_updates(params, updates), opt_state), loss

            (_, _), losses = jax.lax.scan(step, (params, opt_state), None, length=steps)
            return losses

        return np.asarray(train(tokens, labels))

    def test_tp8_loss_decreases(self, rng):
        losses = self._train_losses(tiny_cfg(), rng)
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] - 0.2, losses

    def test_tp8_gqa_loss_decreases(self, rng):
        # REAL GQA under tensor parallelism (groups < heads): 16 q heads
        # share 8 kv heads; over tp=8 each rank holds 2 q heads + 1 kv head
        losses = self._train_losses(
            tiny_cfg(num_attention_heads=16, num_query_groups=8), rng
        )
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] - 0.2, losses

    def test_tp8_sequence_parallel_loss_decreases(self, rng):
        losses = self._train_losses(tiny_cfg(sequence_parallel=True), rng)
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] - 0.2, losses

    @pytest.mark.parametrize("gqa", [False, True])
    def test_tp_kv_cache_decode_matches_full_forward(self, rng, gqa):
        """KV-cache decoding with the cache sharded over tp (heads split
        across ranks): per-step decode logits must equal full-forward
        slices on every rank's vocab shard."""
        mesh = tp_mesh()
        kw = dict(num_attention_heads=16, num_query_groups=8) if gqa else {}
        model = GPTModel(config=tiny_cfg(**kw))
        tokens = jax.random.randint(rng, (2, 12), 0, VOCAB)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def run(tokens):
            variables = model.init(jax.random.PRNGKey(0), tokens[:, :1])
            full = model.apply(variables, tokens)  # (b, 12, vocab_local)
            logits, st = model.apply(
                variables, tokens[:, :5], cache_len=12, mutable=["cache"]
            )
            cache = st["cache"]
            err = jnp.max(jnp.abs(logits - full[:, :5]))
            for pos in range(5, 12):
                sl, upd = model.apply(
                    {**variables, "cache": cache},
                    tokens[:, pos : pos + 1],
                    position_ids=jnp.full((1, 1), pos),
                    decode_step=True,
                    mutable=["cache"],
                )
                cache = upd["cache"]
                err = jnp.maximum(
                    err, jnp.max(jnp.abs(sl[:, 0] - full[:, pos]))
                )
            return jax.lax.pmax(err, "tp")

        assert float(run(tokens)) < 2e-5

    def test_sp_kv_cache_decode_matches_full_forward(self, rng):
        """KV-cache decode under sequence parallelism (formerly a NotImplementedError guard): prefill keeps full SP — the
        column linears gather the sequence, so the cache holds full-length
        K/V — while each decode step runs in plain-TP layout (a single
        replicated token cannot be sequence-sharded).  Per-step decode
        logits must equal full-forward slices on every rank's vocab
        shard."""
        mesh = tp_mesh()
        model = GPTModel(config=tiny_cfg(sequence_parallel=True))
        tokens = jax.random.randint(rng, (2, 16), 0, VOCAB)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def run(tokens):
            variables = model.init(jax.random.PRNGKey(0), tokens[:, :8])
            full = model.apply(variables, tokens)  # (b, 16, vocab_local)
            logits, st = model.apply(
                variables, tokens[:, :8], cache_len=16, mutable=["cache"]
            )
            cache = st["cache"]
            # the SP head gathers the sequence, so prefill logits are
            # full-length just like the uncached forward's
            err = jnp.max(jnp.abs(logits - full[:, :8]))
            for pos in range(8, 16):
                sl, upd = model.apply(
                    {**variables, "cache": cache},
                    tokens[:, pos : pos + 1],
                    position_ids=jnp.full((1, 1), pos),
                    decode_step=True,
                    mutable=["cache"],
                )
                cache = upd["cache"]
                err = jnp.maximum(
                    err, jnp.max(jnp.abs(sl[:, 0] - full[:, pos]))
                )
            return jax.lax.pmax(err, "tp")

        assert float(run(tokens)) < 2e-5

    def test_sp_matches_non_sp(self, rng):
        """Same per-rank params ⇒ identical losses with/without SP (the SP
        mappings are pure re-partitionings; ref mappings.py:213-272)."""
        mesh = tp_mesh()
        tokens = jax.random.randint(rng, (2, 16), 0, VOCAB)
        labels = jnp.roll(tokens, -1, axis=1)
        m_sp = GPTModel(config=tiny_cfg(sequence_parallel=True))
        m_np = GPTModel(config=tiny_cfg(sequence_parallel=False))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )
        def run(tokens, labels):
            params = m_np.init(jax.random.PRNGKey(0), tokens)
            l_np = gpt_loss_fn(m_np.apply(params, tokens, labels=labels))
            l_sp = gpt_loss_fn(m_sp.apply(params, tokens, labels=labels))
            return l_np, l_sp

        l_np, l_sp = run(tokens, labels)
        np.testing.assert_allclose(l_np, l_sp, rtol=1e-5, atol=1e-6)

    def test_bert_sp_loss_and_grads_match_non_sp(self, rng):
        """BERT post-process heads under SP: loss and grads must equal the
        non-SP path with identical per-rank params (guards the dual-head
        gather backward composition in models/bert.py)."""
        from apex_tpu.models import BertModel

        mesh = tp_mesh()
        tokens = jax.random.randint(rng, (2, 16), 0, VOCAB)
        labels = jnp.roll(tokens, -1, axis=1)
        amask = jnp.ones_like(tokens)
        m_sp = BertModel(config=tiny_cfg(sequence_parallel=True))
        m_np = BertModel(config=tiny_cfg(sequence_parallel=False))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P(), P()),
            out_specs=(P(), P(), P(), P()), check_vma=False,
        )
        def run(tokens, labels, amask):
            params = m_np.init(jax.random.PRNGKey(0), tokens, amask)

            def loss_fn(mod, p):
                losses, binary = mod.apply(p, tokens, amask, lm_labels=labels)
                return jnp.mean(losses) + jnp.mean(binary**2)

            l_np, g_np = jax.value_and_grad(lambda p: loss_fn(m_np, p))(params)
            l_sp, g_sp = jax.value_and_grad(lambda p: loss_fn(m_sp, p))(params)

            def gnorm2(g):
                # identical reduction for both paths (psum over tp), so the
                # equality check is valid for sharded and replicated leaves
                total = sum(
                    jnp.sum(x.astype(jnp.float32) ** 2)
                    for x in jax.tree.leaves(g)
                )
                return jax.lax.psum(total, "tp")

            return l_np, l_sp, gnorm2(g_np), gnorm2(g_sp)

        l_np, l_sp, g_np, g_sp = run(tokens, labels, amask)
        np.testing.assert_allclose(l_np, l_sp, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(g_np, g_sp, rtol=1e-4, atol=1e-6)


class TestCheckpointDistributed:
    def test_value_and_grads_match_plain_checkpoint(self, rng):
        """ref random.py:246-266 distribute_saved_activations: partitioning
        the saved boundary activation over tp must not change math."""
        tp = 2
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=tp, devices=jax.devices()[:tp]
        )
        w = jax.random.normal(rng, (16, 16)) * 0.3
        x = jax.random.normal(jax.random.fold_in(rng, 1), (8, 16))

        def fn(x, w):
            return jnp.sum(jnp.tanh(x @ w) ** 2)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )
        def run(x, w):
            return jax.value_and_grad(
                lambda w_: checkpoint_distributed(fn)(x, w_)
            )(w)

        loss, grads = run(x, w)
        ref_loss, ref_grads = jax.value_and_grad(lambda w_: fn(x, w_))(w)
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
        np.testing.assert_allclose(grads, ref_grads, rtol=1e-5, atol=1e-7)

    def test_grad_wrt_boundary_input(self, rng):
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2, devices=jax.devices()[:2]
        )
        x = jax.random.normal(rng, (8, 16))

        def fn(x):
            return jnp.sum(jnp.sin(x) * x)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def run(x):
            return jax.grad(lambda x_: checkpoint_distributed(fn)(x_))(x)

        np.testing.assert_allclose(
            run(x), jax.grad(lambda x_: fn(x_))(x), rtol=1e-5, atol=1e-7
        )


class TestMeshConstruction:
    def test_default_devices_topology_path(self):
        """Default device list goes through mesh_utils (CPU falls back to
        plain order); axis sizes must match the requested factorization."""
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2, pipeline_model_parallel_size=2
        )
        assert dict(mesh.shape) == {"dp": 2, "pp": 2, "cp": 1, "tp": 2}

    def test_hybrid_requires_dp_divisible_by_slices(self):
        with pytest.raises(RuntimeError, match="num_slices"):
            parallel_state.initialize_model_parallel(
                tensor_model_parallel_size=2, num_slices=3
            )

    def test_initialize_distributed_single_process_noop(self, monkeypatch):
        """No args + no cluster env = deterministic no-op, even with
        backends long since initialized — no exception matching. (The
        cluster vars are scrubbed: a single TPU host may export
        TPU_WORKER_HOSTNAMES without being a multi-host cluster.)"""
        for v in ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
                  "SLURM_JOB_ID", "TPU_WORKER_HOSTNAMES",
                  "MEGASCALE_COORDINATOR_ADDRESS"):
            monkeypatch.delenv(v, raising=False)
        n, i = parallel_state.initialize_distributed()
        assert (n, i) == (jax.process_count(), jax.process_index())
        # idempotent second call
        assert parallel_state.initialize_distributed() == (n, i)

    def test_hybrid_rejects_explicit_devices(self):
        with pytest.raises(ValueError, match="explicit devices"):
            parallel_state.initialize_model_parallel(
                devices=jax.devices()[:4], num_slices=2
            )


class TestAmaxReduction:
    def test_pmax_over_dp_and_tp(self, rng):
        """Ref parallel_state.py:280-292: the amax group spans tp x dp
        within a pipeline stage — every rank holding a shard of the same
        activations agrees on one scaling statistic."""
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2, pipeline_model_parallel_size=2
        )

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=P("dp", "tp"), out_specs=P("dp", "tp"),
            check_vma=False,
        )
        def reduce(x):
            return parallel_state.amax_reduction(jnp.max(jnp.abs(x)))[
                None, None
            ]

        x = jax.random.normal(rng, (4, 8))
        out = np.asarray(reduce(x))
        # every (dp, tp) shard agrees on the global max over dp x tp shards
        assert (out == out.flat[0]).all()
        np.testing.assert_allclose(out.flat[0], np.abs(np.asarray(x)).max(),
                                   rtol=1e-6)

    def test_trivial_axes_are_noop_outside_shard_map(self):
        """With every amax axis trivial (dp=cp=tp=1, all devices on pp) the
        host-view call is well-defined and passes through."""
        parallel_state.initialize_model_parallel(
            pipeline_model_parallel_size=8
        )
        v = jnp.asarray(3.0)
        np.testing.assert_allclose(parallel_state.amax_reduction(v), 3.0)

    def test_misuse_outside_shard_map_raises(self):
        """Outside shard_map over a >1 axis the statistic would silently
        miss the other shards — hardened to raise."""
        parallel_state.initialize_model_parallel()  # dp=8
        with pytest.raises(RuntimeError, match="outside shard_map"):
            parallel_state.amax_reduction(jnp.asarray(3.0))


class TestRankAccessorMisuse:
    """Mesh accessors must raise on host-view misuse, not act as rank 0."""

    def test_rank_outside_shard_map_raises(self):
        parallel_state.initialize_model_parallel(tensor_model_parallel_size=8)
        with pytest.raises(RuntimeError, match="outside shard_map"):
            parallel_state.get_tensor_model_parallel_rank()

    def test_trivial_axis_rank_is_zero(self):
        parallel_state.initialize_model_parallel(tensor_model_parallel_size=8)
        assert parallel_state.get_data_parallel_rank() == 0  # dp == 1

    def test_rank_inside_shard_map_still_works(self):
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=8
        )

        @jax.jit
        @functools.partial(shard_map, mesh=mesh, in_specs=P("tp"),
                           out_specs=P("tp"), check_vma=False)
        def ranks(x):
            return x + parallel_state.get_tensor_model_parallel_rank()

        out = np.asarray(ranks(jnp.zeros(8, jnp.int32)))
        np.testing.assert_array_equal(out, np.arange(8))

    def test_tp_rank_init_outside_shard_map_raises(self):
        from apex_tpu.parallel.layers import tp_rank_init

        parallel_state.initialize_model_parallel(tensor_model_parallel_size=8)
        init = tp_rank_init(jax.nn.initializers.normal())
        with pytest.raises(RuntimeError, match="outside shard_map"):
            init(jax.random.PRNGKey(0), (4, 4))
