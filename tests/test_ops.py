"""Tests for softmax family, RoPE, xentropy, fused dense, MLP, flash attention.

Mirrors reference tests/L0/run_transformer/test_fused_softmax.py,
test_fused_rope.py, contrib/test/xentropy, contrib/test/fmha,
tests/L0/run_mlp/test_mlp.py — numeric comparison against straightforward
compositions.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import (
    scaled_softmax,
    scaled_masked_softmax,
    scaled_upper_triang_masked_softmax,
    fused_scale_mask_softmax,
    apply_rotary_pos_emb,
    rope_frequencies,
    softmax_cross_entropy_loss,
    fused_dense,
    fused_dense_gelu_dense,
    mlp_init,
    mlp_apply,
    flash_attention,
)


class TestSoftmax:
    def test_scaled_softmax(self, rng):
        x = jax.random.normal(rng, (2, 4, 8, 8))
        out = scaled_softmax(x, 0.5)
        ref = jax.nn.softmax(x * 0.5, axis=-1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    def test_scaled_masked_softmax(self, rng):
        k1, k2 = jax.random.split(rng)
        x = jax.random.normal(k1, (2, 4, 8, 8))
        mask = jax.random.bernoulli(k2, 0.3, (2, 1, 8, 8))
        out = scaled_masked_softmax(x, mask, 2.0)
        ref = jax.nn.softmax(jnp.where(mask, -10000.0, x * 2.0), axis=-1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    def test_causal_softmax_masks_future(self, rng):
        x = jax.random.normal(rng, (3, 8, 8))
        out = np.asarray(scaled_upper_triang_masked_softmax(x, 1.0))
        # strictly-upper entries must be ~0
        upper = np.triu(np.ones((8, 8)), k=1).astype(bool)
        assert np.all(out[:, upper] < 1e-3)
        np.testing.assert_allclose(out.sum(-1), 1.0, atol=1e-5)

    def test_dispatcher_causal_matches(self, rng):
        x = jax.random.normal(rng, (2, 4, 8, 8))
        out = fused_scale_mask_softmax(x, scale=0.7, causal=True)
        ref = scaled_upper_triang_masked_softmax(x.reshape(8, 8, 8), 0.7).reshape(
            2, 4, 8, 8
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


class TestRope:
    def test_rope_shapes_and_norm_preserved(self, rng):
        t = jax.random.normal(rng, (16, 2, 4, 32))  # (s, b, h, d)
        freqs = rope_frequencies(32, 16)
        out = apply_rotary_pos_emb(t, freqs)
        assert out.shape == t.shape
        # rotation preserves per-pair norms -> total norm preserved
        np.testing.assert_allclose(
            float(jnp.linalg.norm(out)), float(jnp.linalg.norm(t)), rtol=1e-5
        )

    def test_rope_partial_rotation_passthrough(self, rng):
        t = jax.random.normal(rng, (8, 1, 2, 64))
        freqs = rope_frequencies(32, 8)
        out = apply_rotary_pos_emb(t, freqs)
        np.testing.assert_allclose(
            np.asarray(out[..., 32:]), np.asarray(t[..., 32:]), atol=1e-7
        )

    def test_rope_position_zero_identity(self, rng):
        t = jax.random.normal(rng, (4, 1, 1, 16))
        freqs = rope_frequencies(16, 4)
        out = apply_rotary_pos_emb(t, freqs)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(t[0]), atol=1e-6)


class TestXentropy:
    def test_matches_manual_ce(self, rng):
        k1, k2 = jax.random.split(rng)
        logits = jax.random.normal(k1, (10, 50))
        labels = jax.random.randint(k2, (10,), 0, 50)
        loss = softmax_cross_entropy_loss(logits, labels)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ref = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref), atol=1e-5)

    def test_label_smoothing(self, rng):
        k1, k2 = jax.random.split(rng)
        logits = jax.random.normal(k1, (10, 50))
        labels = jax.random.randint(k2, (10,), 0, 50)
        s = 0.1
        loss = softmax_cross_entropy_loss(logits, labels, smoothing=s)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        smooth = -jnp.mean(logp, axis=-1)
        ref = (1 - s) * nll + s * smooth
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref), atol=1e-5)

    def test_grad_is_softmax_minus_onehot(self, rng):
        logits = jax.random.normal(rng, (4, 10))
        labels = jnp.array([1, 2, 3, 4])
        g = jax.grad(lambda l: softmax_cross_entropy_loss(l, labels).sum())(logits)
        p = jax.nn.softmax(logits, -1)
        onehot = jax.nn.one_hot(labels, 10)
        np.testing.assert_allclose(np.asarray(g), np.asarray(p - onehot), atol=1e-5)


class TestDenseMlp:
    def test_fused_dense(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        x = jax.random.normal(k1, (5, 16))
        w = jax.random.normal(k2, (8, 16))
        b = jax.random.normal(k3, (8,))
        np.testing.assert_allclose(
            np.asarray(fused_dense(x, w, b)), np.asarray(x @ w.T + b), atol=1e-5
        )

    def test_fused_dense_gelu_dense(self, rng):
        ks = jax.random.split(rng, 5)
        x = jax.random.normal(ks[0], (5, 16))
        w1 = jax.random.normal(ks[1], (32, 16))
        b1 = jax.random.normal(ks[2], (32,))
        w2 = jax.random.normal(ks[3], (8, 32))
        b2 = jax.random.normal(ks[4], (8,))
        out = fused_dense_gelu_dense(x, w1, b1, w2, b2)
        ref = jax.nn.gelu(x @ w1.T + b1, approximate=True) @ w2.T + b2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_mlp_matches_manual(self, rng):
        params = mlp_init(rng, [16, 32, 32, 4])
        x = jax.random.normal(jax.random.PRNGKey(5), (7, 16))
        out = mlp_apply(params, x, activation="relu")
        h = x
        for i, (w, b) in enumerate(zip(params["weights"], params["biases"])):
            h = h @ w.T + b
            if i < 2:
                h = jax.nn.relu(h)
        np.testing.assert_allclose(np.asarray(out), np.asarray(h), atol=1e-5)

    def test_mlp_grad_flows(self, rng):
        params = mlp_init(rng, [8, 16, 4])
        x = jax.random.normal(jax.random.PRNGKey(3), (5, 8))
        g = jax.grad(lambda p: jnp.sum(mlp_apply(p, x) ** 2))(params)
        assert all(
            float(jnp.abs(gw).sum()) > 0 for gw in jax.tree_util.tree_leaves(g)
        )


class TestFlashAttention:
    def _ref(self, q, k, v, causal):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
        if causal:
            sq, sk = s.shape[-2:]
            cm = jnp.arange(sk)[None, :] > jnp.arange(sq)[:, None]
            s = jnp.where(cm, -1e30, s)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_forward(self, rng, causal, impl):
        k1, k2, k3 = jax.random.split(rng, 3)
        q = jax.random.normal(k1, (2, 2, 256, 64))
        k = jax.random.normal(k2, (2, 2, 256, 64))
        v = jax.random.normal(k3, (2, 2, 256, 64))
        out = flash_attention(q, k, v, causal=causal, impl=impl)
        ref = self._ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("h_kv", [4, 2, 1])
    def test_blockwise_matches_xla(self, rng, causal, h_kv):
        """Long-context tiled path vs the dense reference: forced via
        impl='blockwise' with small tiles so several (cq, ck) chunks and
        the band bounds are actually exercised."""
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        q = jax.random.normal(k1, (2, 4, 256, 32))
        k = jax.random.normal(k2, (2, h_kv, 256, 32))
        v = jax.random.normal(k3, (2, h_kv, 256, 32))
        out = flash_attention(q, k, v, causal=causal, impl="blockwise",
                              block_q=8, block_k=8)  # cq = ck = 64
        ref = flash_attention(q, k, v, causal=causal, impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

        ct = jax.random.normal(k4, q.shape)

        def loss(impl):
            def f(q, k, v):
                o = flash_attention(q, k, v, causal=causal, impl=impl,
                                    block_q=8, block_k=8)
                return jnp.sum(o * ct)
            return f

        gb = jax.grad(loss("blockwise"), (0, 1, 2))(q, k, v)
        gr = jax.grad(loss("xla"), (0, 1, 2))(q, k, v)
        for a, b in zip(gb, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)

    def test_blockwise_window_and_kpm(self, rng):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        q = jax.random.normal(k1, (2, 2, 256, 32))
        k = jax.random.normal(k2, (2, 2, 256, 32))
        v = jax.random.normal(k3, (2, 2, 256, 32))
        out = flash_attention(q, k, v, causal=True, window=100,
                              impl="blockwise", block_q=8, block_k=8)
        ref = flash_attention(q, k, v, causal=True, window=100, impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

        kpm = jnp.zeros((2, 256), bool).at[0, 180:].set(True).at[1, :].set(True)
        out = flash_attention(q, k, v, key_padding_mask=kpm,
                              impl="blockwise", block_q=8, block_k=8)
        ref = flash_attention(q, k, v, key_padding_mask=kpm, impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        # fully-padded batch row -> exact zeros (kernel-path contract)
        assert not np.any(np.asarray(out)[1])

        ct = jax.random.normal(k4, q.shape)
        gb = jax.grad(lambda q: jnp.sum(ct * flash_attention(
            q, k, v, key_padding_mask=kpm, impl="blockwise",
            block_q=8, block_k=8)))(q)
        gr = jax.grad(lambda q: jnp.sum(ct * flash_attention(
            q, k, v, key_padding_mask=kpm, impl="xla")))(q)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(gr), atol=5e-5)

    def test_blockwise_non_divisible_lengths(self, rng):
        """Prime sequence lengths must run padded full-size tiles, not
        degrade the chunk toward 1 (advisor finding r3): sq=131, sk=257
        have no useful divisors, so this exercises the front-padding path
        (pq, pk > 0) including causal band alignment, window, kpm, grads."""
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        q = jax.random.normal(k1, (2, 4, 131, 32))
        k = jax.random.normal(k2, (2, 2, 257, 32))
        v = jax.random.normal(k3, (2, 2, 257, 32))
        for kwargs in ({}, {"causal": True}, {"causal": True, "window": 60}):
            out = flash_attention(q, k, v, impl="blockwise",
                                  block_q=8, block_k=8, **kwargs)
            ref = flash_attention(q, k, v, impl="xla", **kwargs)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5, err_msg=str(kwargs))

        kpm = jnp.zeros((2, 257), bool).at[0, 200:].set(True)
        out = flash_attention(q, k, v, key_padding_mask=kpm,
                              impl="blockwise", block_q=8, block_k=8)
        ref = flash_attention(q, k, v, key_padding_mask=kpm, impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

        ct = jax.random.normal(k4, q.shape)
        gb = jax.grad(lambda q, k, v: jnp.sum(ct * flash_attention(
            q, k, v, causal=True, impl="blockwise", block_q=8, block_k=8)),
            (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(ct * flash_attention(
            q, k, v, causal=True, impl="xla")), (0, 1, 2))(q, k, v)
        for a, b in zip(gb, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)

    def test_blockwise_rectangular_causal(self, rng):
        # sq != sk causal (bottom-right aligned) — the kernel path refuses
        # this; blockwise covers it exactly
        k1, k2, k3 = jax.random.split(rng, 3)
        q = jax.random.normal(k1, (1, 2, 64, 32))
        k = jax.random.normal(k2, (1, 2, 256, 32))
        v = jax.random.normal(k3, (1, 2, 256, 32))
        out = flash_attention(q, k, v, causal=True, impl="blockwise",
                              block_q=4, block_k=8)
        ref = flash_attention(q, k, v, causal=True, impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_long_context_autodispatch(self, rng, monkeypatch):
        """Past the VMEM-residency / score-tensor budgets, auto dispatch
        must pick the tiled path (budgets shrunk so the test stays small)."""
        import apex_tpu.ops.attention as attn_mod

        called = {}
        real = attn_mod._attn_blockwise

        def spy(*a, **kw):
            called["yes"] = True
            return real(*a, **kw)

        monkeypatch.setattr(attn_mod, "_attn_blockwise", spy)
        monkeypatch.setattr(attn_mod, "_SCORE_BYTES", 1024)
        k1, k2, k3 = jax.random.split(rng, 3)
        q = jax.random.normal(k1, (1, 2, 128, 32))
        k = jax.random.normal(k2, (1, 2, 128, 32))
        v = jax.random.normal(k3, (1, 2, 128, 32))
        out = flash_attention(q, k, v, causal=True, impl="xla")
        assert called.get("yes"), "oversized XLA case did not tile"
        ref = self._ref(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_xla(self, rng, causal):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        shape = (1, 2, 128, 64)
        q = jax.random.normal(k1, shape)
        k = jax.random.normal(k2, shape)
        v = jax.random.normal(k3, shape)
        ct = jax.random.normal(k4, shape)

        def loss(impl):
            return lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=causal, impl=impl) * ct
            )

        gp = jax.grad(loss("pallas"), (0, 1, 2))(q, k, v)
        gr = jax.grad(loss("xla"), (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)

    @pytest.mark.parametrize("window", [1, 64, 200, 1000])
    def test_sliding_window_matches_dense_mask(self, rng, window):
        """Windowed-causal (mistral) vs an explicit band mask through the
        dense reference — windows below, straddling, and beyond the 128
        block size, plus the degenerate window=1 (self-only) and a window
        larger than the sequence (== plain causal)."""
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        shape = (2, 2, 256, 64)
        q = jax.random.normal(k1, shape)
        k = jax.random.normal(k2, shape)
        v = jax.random.normal(k3, shape)
        ct = jax.random.normal(k4, shape)

        sq = shape[2]
        rows = jnp.arange(sq)[:, None]
        cols = jnp.arange(sq)[None, :]
        band = jnp.logical_or(cols > rows, cols <= rows - window)

        out = flash_attention(q, k, v, causal=True, window=window, impl="pallas")
        ref = flash_attention(q, k, v, mask=band[None, None], impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

        gp = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True, window=window,
                                impl="pallas") * ct
            ),
            (0, 1, 2),
        )(q, k, v)
        gr = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, mask=band[None, None], impl="xla") * ct
            ),
            (0, 1, 2),
        )(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("h_kv", [1, 2])
    def test_gqa_matches_broadcast_reference(self, rng, causal, h_kv):
        """Grouped-query attention: kv with h_kv heads through the Pallas
        kernels must equal full attention over explicitly repeated kv heads
        (consecutive llama grouping), fwd and all grads — including the
        group-sum of the per-q-head dk/dv partials."""
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        h, sq, d = 4, 128, 64
        q = jax.random.normal(k1, (2, h, sq, d))
        k = jax.random.normal(k2, (2, h_kv, sq, d))
        v = jax.random.normal(k3, (2, h_kv, sq, d))
        ct = jax.random.normal(k4, (2, h, sq, d))
        group = h // h_kv
        k_rep = jnp.repeat(k, group, axis=1)
        v_rep = jnp.repeat(v, group, axis=1)

        out = flash_attention(q, k, v, causal=causal, impl="pallas")
        ref = flash_attention(q, k_rep, v_rep, causal=causal, impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

        def loss(impl, grouped):
            def f(q, k, v):
                o = flash_attention(q, k, v, causal=causal, impl=impl)
                return jnp.sum(o * ct)

            return f

        gq, gk, gv = jax.grad(loss("pallas", True), (0, 1, 2))(q, k, v)
        rq, rk_rep, rv_rep = jax.grad(loss("xla", False), (0, 1, 2))(
            q, k_rep, v_rep
        )
        # repeated-kv reference grads sum over each group
        rk = rk_rep.reshape(2, h_kv, group, sq, d).sum(axis=2)
        rv = rv_rep.reshape(2, h_kv, group, sq, d).sum(axis=2)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=5e-5)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), atol=5e-5)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=5e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_key_padding_mask_matches_xla(self, rng, causal):
        """Pallas fast path with (b, sk) key padding — the reference fmha's
        variable-seqlen capability. One batch row is fully padded to pin the
        exp(-inf - lse) guard."""
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        shape = (3, 2, 128, 64)
        q = jax.random.normal(k1, shape)
        k = jax.random.normal(k2, shape)
        v = jax.random.normal(k3, shape)
        ct = jax.random.normal(k4, shape)
        # row 0: valid prefix 70; row 1: no padding; row 2: ALL padded
        kpm = np.zeros((3, 128), bool)
        kpm[0, 70:] = True
        kpm[2, :] = True
        kpm = jnp.asarray(kpm)

        out_p = flash_attention(q, k, v, causal=causal,
                                key_padding_mask=kpm, impl="pallas")
        out_x = flash_attention(q, k, v, causal=causal,
                                key_padding_mask=kpm, impl="xla")
        # fully-padded rows are ZERO in both impls (no uniform-softmax
        # leakage of padded v values), finite everywhere, never nan
        assert bool(jnp.all(jnp.isfinite(out_p)))
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=5e-5)
        np.testing.assert_allclose(np.asarray(out_p[2]), 0.0, atol=0.0)

        # grads INCLUDE the dead row's output in the loss on purpose: the
        # o=0 convention must be differentiable-consistent (all-zero grads
        # for that row) in BOTH impls, not just when the loss masks it
        def loss(impl):
            def f(q, k, v):
                o = flash_attention(q, k, v, causal=causal,
                                    key_padding_mask=kpm, impl=impl)
                return jnp.sum(o * ct)

            return f

        gp = jax.grad(loss("pallas"), (0, 1, 2))(q, k, v)
        gx = jax.grad(loss("xla"), (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gx):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
        # the dead batch row's q/k/v receive exactly zero gradient
        for a in gp:
            np.testing.assert_allclose(np.asarray(a[2]), 0.0, atol=0.0)

    def test_bf16_gqa_window_compose(self, rng):
        """All three fast-path features at once — bf16 operands, grouped kv,
        sliding window — against the fp32 repeated-kv dense-band reference."""
        k1, k2, k3 = jax.random.split(rng, 3)
        q = jax.random.normal(k1, (2, 4, 128, 64), jnp.float32)
        k = jax.random.normal(k2, (2, 2, 128, 64), jnp.float32)
        v = jax.random.normal(k3, (2, 2, 128, 64), jnp.float32)

        out_b = flash_attention(
            q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16), causal=True, window=40, impl="pallas",
        )
        rows = jnp.arange(128)[:, None]
        cols = jnp.arange(128)[None, :]
        band = jnp.logical_or(cols > rows, cols <= rows - 40)
        ref = flash_attention(
            q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
            mask=band[None, None], impl="xla",
        )
        assert out_b.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out_b, np.float32), np.asarray(ref), atol=0.08
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_bf16_fwd_bwd_close_to_fp32_ref(self, rng, causal):
        """bf16 path: the kernel keeps dot OPERANDS in bf16 (p and ds are
        cast back down before their dots — the MXU-rate flash recipe) with
        fp32 accumulation/softmax.  Gate: within a few bf16 ulps of the
        all-fp32 reference, fwd and bwd — this is the only test where the
        kernel's bf16 casts are not no-ops."""
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        shape = (1, 2, 128, 64)
        qf = jax.random.normal(k1, shape)
        kf = jax.random.normal(k2, shape)
        vf = jax.random.normal(k3, shape)
        ct = jax.random.normal(k4, shape)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))

        out_b = flash_attention(qb, kb, vb, causal=causal, impl="pallas")
        ref_f = self._ref(qf, kf, vf, causal)
        # |out| <= max|v| ~ 4; bf16 eps ~ 8e-3 -> a few ulps of headroom
        np.testing.assert_allclose(
            np.asarray(out_b, np.float32), np.asarray(ref_f), atol=0.08
        )

        def loss(impl, dt):
            return lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=causal, impl=impl).astype(
                    jnp.float32
                ) * ct
            )

        gb = jax.grad(loss("pallas", jnp.bfloat16), (0, 1, 2))(qb, kb, vb)
        gf = jax.grad(loss("xla", jnp.float32), (0, 1, 2))(qf, kf, vf)
        for a, b in zip(gb, gf):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b), atol=0.35
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_multiblock(self, rng, causal):
        """seq > block forces the backward kernels' inner block loops (and
        the causal lo/hi bounds) to run over several blocks."""
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        shape = (1, 2, 256, 32)
        q = jax.random.normal(k1, shape)
        k = jax.random.normal(k2, shape)
        v = jax.random.normal(k3, shape)
        ct = jax.random.normal(k4, shape)

        def loss(impl):
            return lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=causal, impl=impl,
                                block_q=64, block_k=64) * ct
            )

        gp = jax.grad(loss("pallas"), (0, 1, 2))(q, k, v)
        gr = jax.grad(loss("xla"), (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)

    def test_grads_rectangular_kv(self, rng):
        """sk > sq (cross-attention shape) through the Pallas backward."""
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        q = jax.random.normal(k1, (1, 2, 64, 32))
        k = jax.random.normal(k2, (1, 2, 192, 32))
        v = jax.random.normal(k3, (1, 2, 192, 32))
        ct = jax.random.normal(k4, (1, 2, 64, 32))

        def loss(impl):
            return lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, impl=impl, block_q=64, block_k=64) * ct
            )

        gp = jax.grad(loss("pallas"), (0, 1, 2))(q, k, v)
        gr = jax.grad(loss("xla"), (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)

    @pytest.mark.parametrize("name,sq,sk,d,dtype,window", [
        ("gpt2 345m cell", 1024, 1024, 64, jnp.bfloat16, None),
        ("decode", 1, 1024, 64, jnp.bfloat16, None),
        ("f32 256", 256, 256, 64, jnp.float32, None),
        ("bert 512 key padding", 512, 512, 64, jnp.bfloat16, None),
        ("gqa+window 4096x128", 4096, 4096, 128, jnp.bfloat16, 1024),
        ("narrow window", 4096, 4096, 128, jnp.bfloat16, 200),
        ("cross sq>sk", 2048, 384, 64, jnp.bfloat16, None),
        ("no tile divides", 200, 200, 64, jnp.float32, None),
        ("edge bf16 d=64", 14336, 14336, 64, jnp.bfloat16, None),
        ("edge bf16 d=128", 14336, 14336, 128, jnp.bfloat16, None),
        ("edge f32 d=64", 7168, 7168, 64, jnp.float32, None),
        ("edge f32 d=128", 7168, 7168, 128, jnp.float32, None),
    ])
    def test_tile_rule(self, name, sq, sk, d, dtype, window):
        """``_flash_tiles``: every tile divides its sequence, and no call
        the 128 x 128 kernels took goes to XLA for want of a tile; short
        sequences and the K/V residency edge keep the 128 x 128 of before,
        the shapes with room get wider steps."""
        from apex_tpu.ops import attention as A

        resident = A._kv_vmem_bytes(max(sq, sk), d, jnp.dtype(dtype).itemsize)
        tiles = A._flash_tiles(sq, sk, window, resident)
        before = (min(128, sq), min(128, sk))
        if sq % before[0] or sk % before[1]:
            assert tiles is None
            return
        bq, bk = tiles
        assert sq % bq == 0 and sk % bk == 0
        assert bq >= before[0] and bk >= before[1]
        if min(sq, sk) < 128 or name.startswith("edge"):
            assert tiles == before
        if name == "gpt2 345m cell":
            assert min(tiles) >= 512
        if window is not None:
            assert max(tiles) <= max(128, window // 2)
        # a forced tile is taken as given
        assert A._flash_tiles(sq, sk, window, resident, 128, 128) == before

    @pytest.mark.parametrize("bq,bk,sub", [
        (64, 32, 16), (32, 64, 16), (256, 128, 128), (128, 256, 128)])
    @pytest.mark.parametrize("case", [
        "causal", "causal+window", "key padding", "gqa causal"])
    def test_unequal_tiles_and_subtiles_match_reference(
            self, rng, monkeypatch, case, bq, bk, sub):
        """The kernels' loops split their range into fully visible blocks
        (no mask code) and blocks that cross the diagonal, the window's
        edge or carry padding; a tile is several sub-tiles; the dk/dv
        kernel works on transposed scores. Forward and gradients against
        ``_attn_ref`` with bq > bk and bq < bk (each order puts the
        statically unrolled diagonal in other kernels), two tiles a
        sequence so every loop has a fully visible block, and a fully
        padded batch row."""
        from apex_tpu.ops import attention as A

        monkeypatch.setattr(A, "_SUBTILE", sub)
        s = 2 * max(bq, bk)
        h_kv = 1 if case == "gqa causal" else 2
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        q = jax.random.normal(k1, (3, 2, s, 32))
        k = jax.random.normal(k2, (3, h_kv, s, 32))
        v = jax.random.normal(k3, (3, h_kv, s, 32))
        ct = jax.random.normal(k4, (3, 2, s, 32))
        kw = dict(causal=case != "key padding")
        if case == "causal+window":
            kw["window"] = s // 3 + 1
        if case == "key padding":
            kpm = np.zeros((3, s), bool)
            kpm[0, s // 2 + 5:] = True
            kpm[2, :] = True
            kw["key_padding_mask"] = jnp.asarray(kpm)

        def loss(impl):
            return lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, impl=impl, block_q=bq, block_k=bk, **kw) * ct)

        out = flash_attention(q, k, v, impl="pallas", block_q=bq, block_k=bk,
                              **kw)
        ref = flash_attention(q, k, v, impl="xla", **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)
        gp = jax.grad(loss("pallas"), (0, 1, 2))(q, k, v)
        gr = jax.grad(loss("xla"), (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    def test_mask_path(self, rng):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        q = jax.random.normal(k1, (2, 2, 64, 32))
        k = jax.random.normal(k2, (2, 2, 64, 32))
        v = jax.random.normal(k3, (2, 2, 64, 32))
        mask = jax.random.bernoulli(k4, 0.2, (2, 1, 64, 64))
        out = flash_attention(q, k, v, mask=mask)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(32)
        s = jnp.where(mask, -1e30, s)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


class TestModuleStyleSurfaces:
    """apex.mlp / apex.fused_dense import-surface parity: flax module
    classes over the functional ops (ref mlp/mlp.py:33,
    fused_dense/fused_dense.py:64,82)."""

    def test_mlp_module_matches_functional(self, rng):
        from apex_tpu.mlp import MLP
        from apex_tpu.ops.mlp import mlp_apply

        sizes = [16, 32, 8]
        m = MLP(mlp_sizes=sizes, activation="relu")
        x = jax.random.normal(rng, (4, 16))
        params = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(params, x)
        # rebuild the functional param pytree from the module params
        p = params["params"]
        fparams = {
            "weights": [p["weight_0"], p["weight_1"]],
            "biases": [p["bias_0"], p["bias_1"]],
        }
        ref = mlp_apply(fparams, x, activation="relu")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)
        # init matches reset_parameters (ref mlp/mlp.py:71-79): weights
        # ~ N(0, sqrt(2/(fan_in+fan_out))) — check the std statistically
        w_wide = MLP(mlp_sizes=[256, 256]).init(
            jax.random.PRNGKey(7), jnp.ones((1, 256))
        )["params"]["weight_0"]
        std = float(jnp.std(w_wide))
        expect = (2.0 / 512.0) ** 0.5
        assert abs(std - expect) / expect < 0.1, (std, expect)

    def test_mlp_module_rejects_bad_activation(self, rng):
        from apex_tpu.mlp import MLP

        with pytest.raises(TypeError, match="activation"):
            MLP(mlp_sizes=[4, 4], activation="tanh").init(
                jax.random.PRNGKey(0), jnp.ones((2, 4))
            )

    def test_fused_dense_modules(self, rng):
        from apex_tpu.fused_dense import FusedDense, FusedDenseGeluDense

        x = jax.random.normal(rng, (4, 16))
        m = FusedDense(in_features=16, out_features=8)
        params = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(params, x)
        w = params["params"]["weight"]
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(x @ w.T), atol=1e-5
        )
        m2 = FusedDenseGeluDense(in_features=16, intermediate_features=32,
                                 out_features=8, bias=True)
        p2 = m2.init(jax.random.PRNGKey(1), x)
        out2 = m2.apply(p2, x)
        assert out2.shape == (4, 8) and bool(jnp.all(jnp.isfinite(out2)))
        # reference ctor kwarg: bias=False supported on FusedDense only
        m3 = FusedDense(in_features=16, out_features=8, bias=False)
        p3 = m3.init(jax.random.PRNGKey(2), x)
        assert "bias" not in p3["params"]
