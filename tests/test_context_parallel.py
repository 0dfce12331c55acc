"""Context-parallel (ring / Ulysses) attention parity tests.

No reference counterpart (the reference has no CP — SURVEY.md §2.5); the
test strategy mirrors its fused-vs-reference style: exact parity of outputs
AND gradients against single-device full attention, causal and bidirectional,
on the virtual CPU mesh.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from apex_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.attention import flash_attention
from apex_tpu.parallel import parallel_state
from apex_tpu.parallel.ring_attention import (
    ring_attention,
    ulysses_attention,
    zigzag_shard,
    zigzag_unshard,
)

B, H, D = 2, 4, 8
SEQ = 32


def full_reference(q, k, v, causal):
    return flash_attention(q, k, v, causal=causal, impl="xla")


def seq_spec():
    return P(None, None, "cp", None)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("cp", [4, 8])
    def test_forward_parity(self, rng, causal, cp):
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, H, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, H, SEQ, D), jnp.float32)

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(seq_spec(),) * 3,
            out_specs=seq_spec(),
            check_vma=False,
        )
        def run(q, k, v):
            return ring_attention(q, k, v, axis_name="cp", causal=causal)

        np.testing.assert_allclose(
            run(q, k, v), full_reference(q, k, v, causal), rtol=2e-4, atol=2e-5
        )

    def test_zigzag_shard_roundtrip(self, rng):
        x = jax.random.normal(rng, (B, H, SEQ, D))
        for cp in (2, 4, 8):
            z = zigzag_shard(x, cp)
            assert z.shape == x.shape
            np.testing.assert_array_equal(
                np.asarray(zigzag_unshard(z, cp)), np.asarray(x)
            )
        # rank 0's shard is pieces (0, 2P-1): first piece of the sequence
        # followed by the last
        cp, half = 4, SEQ // 8
        z = zigzag_shard(x, cp)
        np.testing.assert_array_equal(
            np.asarray(z[..., :half, :]), np.asarray(x[..., :half, :])
        )
        np.testing.assert_array_equal(
            np.asarray(z[..., half : 2 * half, :]),
            np.asarray(x[..., -half:, :]),
        )

    @pytest.mark.parametrize("cp", [4, 8])
    @pytest.mark.parametrize("window", [None, 12])
    def test_zigzag_matches_single_device(self, rng, cp, window):
        """Load-balanced layout == contiguous math: zigzag_shard -> ring
        (zigzag=True) -> zigzag_unshard equals full single-device causal
        attention, forward and grads."""
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv, kc = jax.random.split(rng, 4)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, H, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, H, SEQ, D), jnp.float32)
        ct = jax.random.normal(kc, (B, H, SEQ, D), jnp.float32)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(seq_spec(),) * 3,
            out_specs=seq_spec(), check_vma=False,
        )
        def run_local(q, k, v):
            return ring_attention(
                q, k, v, axis_name="cp", causal=True, window=window,
                zigzag=True, block_size=8,
            )

        def run(q, k, v):
            zq, zk, zv = (zigzag_shard(t, cp) for t in (q, k, v))
            return zigzag_unshard(run_local(zq, zk, zv), cp)

        ref = flash_attention(q, k, v, causal=True, window=window, impl="xla")
        np.testing.assert_allclose(
            run(q, k, v), ref, rtol=2e-4, atol=2e-5
        )

        gz = jax.grad(lambda q, k, v: jnp.sum(run(q, k, v) * ct), (0, 1, 2))(
            q, k, v
        )
        gr = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True, window=window,
                                impl="xla") * ct
            ),
            (0, 1, 2),
        )(q, k, v)
        for a, b in zip(gz, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    @pytest.mark.parametrize("window", [3, 12, 100])
    def test_sliding_window_matches_single_device(self, rng, window):
        """Global-position banding across ring chunks: windows inside one
        chunk, spanning chunks, and wider than the sequence (== causal)."""
        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv, kc = jax.random.split(rng, 4)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, H, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, H, SEQ, D), jnp.float32)
        ct = jax.random.normal(kc, (B, H, SEQ, D), jnp.float32)

        def ring_run(window):
            @jax.jit
            @functools.partial(
                shard_map,
                mesh=mesh,
                in_specs=(seq_spec(),) * 3,
                out_specs=seq_spec(),
                check_vma=False,
            )
            def run(q, k, v):
                return ring_attention(
                    q, k, v, axis_name="cp", causal=True, window=window
                )

            return run

        ref = flash_attention(q, k, v, causal=True, window=window, impl="xla")
        np.testing.assert_allclose(
            ring_run(window)(q, k, v), ref, rtol=2e-4, atol=2e-5
        )
        # grads through the banded ring
        gp = jax.grad(
            lambda q, k, v: jnp.sum(ring_run(window)(q, k, v) * ct), (0, 1, 2)
        )(q, k, v)
        gr = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True, window=window,
                                impl="xla") * ct
            ),
            (0, 1, 2),
        )(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    def test_ulysses_sliding_window_matches_single_device(self, rng):
        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, H, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, H, SEQ, D), jnp.float32)

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(seq_spec(),) * 3,
            out_specs=seq_spec(),
            check_vma=False,
        )
        def run(q, k, v):
            return ulysses_attention(
                q, k, v, axis_name="cp", causal=True, window=8
            )

        ref = flash_attention(q, k, v, causal=True, window=8, impl="xla")
        np.testing.assert_allclose(run(q, k, v), ref, rtol=2e-4, atol=2e-5)

    def test_bf16_forward_close_to_fp32_reference(self, rng):
        """bf16 path: einsum operands stay bf16 (MXU-rate policy, as in
        ops/attention.py) with fp32 online-softmax state — the only test
        where those casts are not no-ops."""
        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv = jax.random.split(rng, 3)
        qf = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        kf = jax.random.normal(kk, (B, H, SEQ, D), jnp.float32)
        vf = jax.random.normal(kv, (B, H, SEQ, D), jnp.float32)

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(seq_spec(),) * 3,
            out_specs=seq_spec(),
            check_vma=False,
        )
        def run(q, k, v):
            return ring_attention(q, k, v, axis_name="cp", causal=True)

        out_b = run(*(x.astype(jnp.bfloat16) for x in (qf, kf, vf)))
        ref = full_reference(qf, kf, vf, True)
        assert out_b.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out_b, np.float32), np.asarray(ref), atol=0.08
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_grad_parity(self, rng, causal):
        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv, kt = jax.random.split(rng, 4)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, H, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, H, SEQ, D), jnp.float32)
        tgt = jax.random.normal(kt, (B, H, SEQ, D), jnp.float32)

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(seq_spec(),) * 4,
            out_specs=(P(), (seq_spec(),) * 3),
            check_vma=False,
        )
        def run(q, k, v, tgt):
            def loss(q, k, v):
                o = ring_attention(q, k, v, axis_name="cp", causal=causal)
                # local-mean then sum over cp chunks == global sum scaled;
                # keep the psum off the grad path (shard_map transpose rule)
                l = jnp.sum((o - tgt) ** 2)
                return l + jax.lax.stop_gradient(
                    jax.lax.psum(l, "cp") - l
                )

            l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            return l, grads

        loss, (dq, dk, dv) = run(q, k, v, tgt)

        def ref_loss(q, k, v):
            o = full_reference(q, k, v, causal)
            return jnp.sum((o - tgt) ** 2)

        ref_l, ref_grads = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(loss, ref_l, rtol=1e-4)
        for got, want in zip((dq, dk, dv), ref_grads):
            np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-4)


class TestRingGQAAndKeyPadding:
    """GQA x causal x window x kpm through the ring:
    grouped K/V rotate (not repeated pre-ring), the sequence-sharded
    key_padding_mask rides with its chunk, and an all-padded visiting
    chunk is skipped like an out-of-band one."""

    def _kpm(self):
        # last ring chunk (positions 24..31 at cp=4) fully padded in EVERY
        # batch row -> exercises whole-chunk skipping; row 0 additionally
        # pads a partial tail inside chunk 2
        kpm = jnp.zeros((B, SEQ), bool)
        kpm = kpm.at[:, 24:].set(True).at[0, 20:].set(True)
        return kpm

    @pytest.mark.parametrize("h_kv", [4, 2, 1])
    @pytest.mark.parametrize("causal,window",
                             [(False, None), (True, None), (True, 12)])
    @pytest.mark.parametrize("use_kpm", [False, True])
    def test_parity_and_grads(self, rng, h_kv, causal, window, use_kpm):
        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv, kc = jax.random.split(rng, 4)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, h_kv, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, h_kv, SEQ, D), jnp.float32)
        ct = jax.random.normal(kc, (B, H, SEQ, D), jnp.float32)
        kpm = self._kpm() if use_kpm else None

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(seq_spec(), seq_spec(), seq_spec(), P(None, "cp")),
            out_specs=seq_spec(), check_vma=False,
        )
        def run(q, k, v, kpm):
            return ring_attention(
                q, k, v, axis_name="cp", causal=causal, window=window,
                key_padding_mask=kpm, block_size=8,
            )

        def ring(q, k, v):
            if kpm is None:
                # shard_map in_specs are fixed; route None via a zero mask
                return run(q, k, v, jnp.zeros((B, SEQ), bool))
            return run(q, k, v, kpm)

        ref_fn = lambda q, k, v: flash_attention(
            q, k, v, causal=causal, window=window, key_padding_mask=kpm,
            impl="xla",
        )
        np.testing.assert_allclose(
            ring(q, k, v), ref_fn(q, k, v), rtol=2e-4, atol=2e-5
        )
        gp = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) * ct),
                      (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(ref_fn(q, k, v) * ct),
                      (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    def test_zigzag_gqa_kpm(self, rng):
        """The load-balanced layout composes with GQA + kpm: the mask is
        zigzag-reordered exactly like the keys it pads."""
        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv, kc = jax.random.split(rng, 4)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, 2, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, 2, SEQ, D), jnp.float32)
        ct = jax.random.normal(kc, (B, H, SEQ, D), jnp.float32)
        kpm = self._kpm()

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(seq_spec(), seq_spec(), seq_spec(), P(None, "cp")),
            out_specs=seq_spec(), check_vma=False,
        )
        def run_local(q, k, v, kpm):
            return ring_attention(
                q, k, v, axis_name="cp", causal=True,
                key_padding_mask=kpm, zigzag=True, block_size=8,
            )

        def run(q, k, v):
            zq, zk, zv = (zigzag_shard(t, cp) for t in (q, k, v))
            zm = zigzag_shard(kpm, cp, axis=-1)
            return zigzag_unshard(run_local(zq, zk, zv, zm), cp)

        ref_fn = lambda q, k, v: flash_attention(
            q, k, v, causal=True, key_padding_mask=kpm, impl="xla"
        )
        np.testing.assert_allclose(
            run(q, k, v), ref_fn(q, k, v), rtol=2e-4, atol=2e-5
        )
        gp = jax.grad(lambda q, k, v: jnp.sum(run(q, k, v) * ct),
                      (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(ref_fn(q, k, v) * ct),
                      (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    def test_ring_rejects_indivisible_heads(self, rng):
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=4, devices=jax.devices()[:4]
        )
        q = jnp.zeros((B, 4, SEQ, D))
        k = jnp.zeros((B, 3, SEQ, D))
        with pytest.raises(ValueError, match="not divisible"):

            @jax.jit
            @functools.partial(
                shard_map, mesh=mesh, in_specs=(seq_spec(),) * 3,
                out_specs=seq_spec(), check_vma=False,
            )
            def run(q, k, v):
                return ring_attention(q, k, v, axis_name="cp")

            run(q, k, k)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity(self, rng, causal):
        cp = 4  # heads=4 divisible by cp
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, H, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, H, SEQ, D), jnp.float32)

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(seq_spec(),) * 3,
            out_specs=seq_spec(),
            check_vma=False,
        )
        def run(q, k, v):
            return ulysses_attention(q, k, v, axis_name="cp", causal=causal)

        np.testing.assert_allclose(
            run(q, k, v), full_reference(q, k, v, causal), rtol=2e-4, atol=2e-5
        )

    def test_gqa_and_kpm_parity(self, rng):
        """GQA K/V (kv_heads % cp == 0) plus an all-gathered sequence-
        sharded key-padding mask through the all-to-all path."""
        cp = 2
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv, kc = jax.random.split(rng, 4)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, 2, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, 2, SEQ, D), jnp.float32)
        ct = jax.random.normal(kc, (B, H, SEQ, D), jnp.float32)
        kpm = jnp.zeros((B, SEQ), bool).at[0, 20:].set(True)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(seq_spec(), seq_spec(), seq_spec(), P(None, "cp")),
            out_specs=seq_spec(), check_vma=False,
        )
        def run(q, k, v, kpm):
            return ulysses_attention(
                q, k, v, axis_name="cp", causal=True, key_padding_mask=kpm
            )

        ref_fn = lambda q, k, v: flash_attention(
            q, k, v, causal=True, key_padding_mask=kpm, impl="xla"
        )
        np.testing.assert_allclose(
            run(q, k, v, kpm), ref_fn(q, k, v), rtol=2e-4, atol=2e-5
        )
        gp = jax.grad(lambda q, k, v: jnp.sum(run(q, k, v, kpm) * ct),
                      (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(ref_fn(q, k, v) * ct),
                      (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    def test_grad_flows(self, rng):
        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        q = jax.random.normal(rng, (B, H, SEQ, D), jnp.float32)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=seq_spec(),
            out_specs=seq_spec(),
            check_vma=False,
        )
        def grad_q(q):
            def loss(q):
                o = ulysses_attention(q, q, q, axis_name="cp", causal=True)
                l = jnp.sum(o**2)
                return l + jax.lax.stop_gradient(jax.lax.psum(l, "cp") - l)

            return jax.grad(loss)(q)

        def ref(q):
            return jnp.sum(full_reference(q, q, q, True) ** 2)

        np.testing.assert_allclose(
            grad_q(q), jax.grad(ref)(q), rtol=2e-3, atol=1e-4
        )


class TestGPTWithCP:
    @pytest.mark.parametrize("pos_emb", ["rope", "learned"])
    def test_gpt_ring_cp_matches_single_device(self, rng, pos_emb):
        """End-to-end: GPT with context_parallel_mode='ring' on a cp=4 mesh
        reproduces single-device per-token losses (both rotary and learned
        positions — the latter must offset by the cp rank)."""
        from apex_tpu.models import GPTModel
        from apex_tpu.transformer import TransformerConfig

        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )

        def cfg(cp_mode):
            return TransformerConfig(
                num_layers=2,
                hidden_size=32,
                num_attention_heads=4,
                vocab_size=64,
                max_position_embeddings=SEQ,
                hidden_dropout=0.0,
                attention_dropout=0.0,
                position_embedding_type=pos_emb,
                compute_dtype=jnp.float32,
                context_parallel_mode=cp_mode,
            )

        tokens = jax.random.randint(rng, (2, SEQ), 0, 64)
        labels = jnp.roll(tokens, -1, axis=1)

        ref_model = GPTModel(config=cfg(None))
        params = ref_model.init(jax.random.PRNGKey(1), tokens)
        ref_losses = ref_model.apply(params, tokens, labels=labels)

        cp_model = GPTModel(config=cfg("ring"))

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(None, "cp"), P(None, "cp")),
            out_specs=P(None, "cp"),
            check_vma=False,
        )
        def run(params, tokens, labels):
            return cp_model.apply(params, tokens, labels=labels)

        cp_losses = run(params, tokens, labels)
        np.testing.assert_allclose(cp_losses, ref_losses, rtol=2e-4, atol=2e-5)


class TestCPDecode:
    def test_gpt_ring_cp_kv_cache_decode_matches_single_device(self, rng):
        """KV-cache decode over a context-parallel-sharded cache (VERDICT
        r4 item 8, formerly a NotImplementedError guard): prefill writes
        each rank's contiguous prompt shard into its local cache, decode
        tokens land round-robin (token t -> rank t % cp), and each step
        merges per-rank partial softmax stats via cp_decode_attention's
        log-sum-exp identity.  Per-step logits must equal the
        single-device uncached forward at every decoded position."""
        from apex_tpu.models import GPTModel
        from apex_tpu.transformer import TransformerConfig

        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        total, prompt = 16, 8

        def cfg(cp_mode):
            return TransformerConfig(
                num_layers=2,
                hidden_size=32,
                num_attention_heads=4,
                vocab_size=64,
                max_position_embeddings=total,
                hidden_dropout=0.0,
                attention_dropout=0.0,
                position_embedding_type="rope",
                compute_dtype=jnp.float32,
                context_parallel_mode=cp_mode,
            )

        tokens = jax.random.randint(rng, (2, total), 0, 64)
        ref_model = GPTModel(config=cfg(None))
        params = ref_model.init(jax.random.PRNGKey(1), tokens)
        full = np.asarray(ref_model.apply(params, tokens))  # (b, total, v)
        cp_model = GPTModel(config=cfg("ring"))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False,
        )
        def decode_all(params, tokens):
            r = jax.lax.axis_index("cp")
            s_local = prompt // cp
            local = jax.lax.dynamic_slice_in_dim(
                tokens[:, :prompt], r * s_local, s_local, 1
            )
            _, st = cp_model.apply(
                params, local, cache_len=total, mutable=["cache"]
            )
            cache = st["cache"]
            outs = []
            for pos in range(prompt, total):
                sl, upd = cp_model.apply(
                    {**params, "cache": cache},
                    tokens[:, pos : pos + 1],
                    decode_step=True,
                    mutable=["cache"],
                )
                cache = upd["cache"]
                outs.append(sl[:, 0])
            return jnp.stack(outs, axis=1)  # (b, total-prompt, v)

        got = np.asarray(decode_all(params, tokens))
        np.testing.assert_allclose(
            got, full[:, prompt:], rtol=2e-4, atol=2e-4
        )


class TestRingBlockwise:
    @pytest.mark.parametrize("block_size", [2, 4, 8])
    def test_inner_blocking_matches(self, rng, block_size):
        """block_size < s_local exercises the inner kv-block scan (the
        O(s x block) memory path) — results must be block-size invariant."""
        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (B, H, SEQ, D), jnp.float32)
        k = jax.random.normal(kk, (B, H, SEQ, D), jnp.float32)
        v = jax.random.normal(kv, (B, H, SEQ, D), jnp.float32)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(seq_spec(),) * 3,
            out_specs=(seq_spec(),) * 3, check_vma=False,
        )
        def run(q, k, v):
            def loss(q, k, v):
                o = ring_attention(
                    q, k, v, axis_name="cp", causal=True, block_size=block_size
                )
                l = jnp.sum(o**2)
                return l + jax.lax.stop_gradient(jax.lax.psum(l, "cp") - l)

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        def ref(q, k, v):
            return jnp.sum(full_reference(q, k, v, True) ** 2)

        got = run(q, k, v)
        want = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=2e-3, atol=1e-4)


class TestShardAwareDropout:
    def test_masks_differ_across_cp_ranks(self, rng):
        from apex_tpu.transformer.layer import ShardAwareDropout

        cp = 4
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=cp, devices=jax.devices()[:cp]
        )
        mod = ShardAwareDropout(rate=0.5, axis_names=("cp",))
        x = jnp.ones((4, 64))

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P("cp"),
            check_vma=False,
        )
        def run(x):
            y = mod.apply({}, x, deterministic=False,
                          rngs={"dropout": jax.random.PRNGKey(7)})
            return y[None]

        per_rank = run(x)  # (cp, 4, 64) — same input, same key, per-rank mask
        masks = np.asarray(per_rank) != 0.0
        assert not all(
            np.array_equal(masks[0], masks[i]) for i in range(1, cp)
        ), "cp ranks drew identical dropout masks"

    def test_identity_without_axes(self, rng):
        from apex_tpu.transformer.layer import ShardAwareDropout

        mod = ShardAwareDropout(rate=0.5, axis_names=("cp",))
        x = jnp.ones((8, 8))
        # outside shard_map the unbound axis is skipped, not an error
        y = mod.apply({}, x, deterministic=False,
                      rngs={"dropout": jax.random.PRNGKey(0)})
        assert y.shape == x.shape
        z = mod.apply({}, x, deterministic=True)
        np.testing.assert_array_equal(z, x)


class TestCPComposition:
    """cp composed with tp sequence parallelism — the axis combination
    Megatron-style long-context training actually runs (no reference
    counterpart).  Parity target: the tp-only run on the same mesh — that
    path is itself pinned to the single-device model by the tp test suite,
    so this test isolates exactly what turning cp on changes."""

    @pytest.mark.parametrize("sp", [False, True])
    def test_gpt_cp_tp_sp_matches_tp_only(self, rng, sp):
        from apex_tpu.models import GPTModel
        from apex_tpu.transformer import TransformerConfig

        cp, tp = 2, 2
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=tp, context_parallel_size=cp,
            devices=jax.devices()[: cp * tp * 2],  # dp=2 as well
        )

        def cfg(cp_mode, sp_flag):
            return TransformerConfig(
                num_layers=2,
                hidden_size=32,
                num_attention_heads=4,
                num_query_groups=2,  # GQA through the ring
                vocab_size=64,
                max_position_embeddings=SEQ,
                hidden_dropout=0.0,
                attention_dropout=0.0,
                compute_dtype=jnp.float32,
                context_parallel_mode=cp_mode,
                sequence_parallel=sp_flag,
            )

        tokens = jax.random.randint(rng, (4, SEQ), 0, 64)
        labels = jnp.roll(tokens, -1, axis=1)

        cp_model = GPTModel(config=cfg("ring", sp))

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P("dp", "cp"), P("dp", "cp")),
            out_specs=P("dp", "cp"),
            check_vma=False,
        )
        def run(params, tokens, labels):
            return cp_model.apply(params, tokens, labels=labels)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def init(tokens):
            return cp_model.init(jax.random.PRNGKey(1), tokens)

        params = init(tokens[:1, : SEQ // cp])
        cp_losses = run(params, tokens, labels)

        # reference: the tp-only run (cp disabled) with the SAME params on
        # the same mesh — tp shards live per-rank so a true single-device
        # evaluation cannot consume them; the tp path itself is pinned to
        # single-device by tests/test_tensor_parallel.py
        tp_model = GPTModel(config=cfg(None, sp))

        @jax.jit
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P("dp"), P("dp")),
            out_specs=P("dp"),
            check_vma=False,
        )
        def run_tp(params, tokens, labels):
            return tp_model.apply(params, tokens, labels=labels)

        tp_losses = run_tp(params, tokens, labels)
        np.testing.assert_allclose(
            np.asarray(cp_losses), np.asarray(tp_losses),
            rtol=2e-4, atol=2e-5,
        )
