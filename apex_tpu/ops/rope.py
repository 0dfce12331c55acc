"""Rotary position embedding.

Reference parity: ``fused_rotary_positional_embedding``
(csrc/megatron/fused_rotary_positional_embedding.cpp:126-133) and the autograd
wrappers FusedRoPEFunc / FusedRoPECachedFunc
(transformer/functional/fused_rope.py:19,80).

On TPU the rotate-half + cos/sin multiply is a pure VPU elementwise chain that
XLA fuses into the surrounding attention projections, so no Pallas kernel is
needed; the "cached" variant is just precomputing cos/sin once per step
(rope_frequencies), which jit hoists automatically.

Layout follows the reference: ``t`` is (seq, batch, heads, head_dim) and
``freqs`` is (seq, 1, 1, rot_dim).
"""

import jax.numpy as jnp


def rope_frequencies(dim: int, seq_len: int, base: float = 10000.0,
                     dtype=jnp.float32, interleaved: bool = False):
    """Build the (seq, 1, 1, dim) angle tensor (ref: RotaryEmbedding in
    testing/standalone_transformer_lm.py; freqs duplicated across halves).
    ``interleaved``: channel pair (2i, 2i+1) carries angle i (the layout
    ``apply_rotary_pos_emb(..., interleaved=True)`` rotates)."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # (seq, dim/2)
    if interleaved:
        emb = jnp.repeat(freqs, 2, axis=-1)  # (seq, dim): f0 f0 f1 f1 ...
    else:
        emb = jnp.concatenate([freqs, freqs], axis=-1)  # (seq, dim)
    return emb[:, None, None, :].astype(dtype)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotate_pairs(x):
    """(x0, x1, x2, x3, ...) -> (-x1, x0, -x3, x2, ...): the partner of
    each channel in a rotation of consecutive pairs."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    return (pairs[..., ::-1] * jnp.array([-1, 1], x.dtype)).reshape(x.shape)


def apply_rotary_pos_emb_cached(t, cos_, sin_):
    """Cached-cos/sin RoPE (ref: fused_apply_rotary_pos_emb_cached,
    transformer/functional/fused_rope.py:121 — t (s, b, h, d), cos_/sin_
    (s, 1, 1, rot_dim)).  ``transpose_output_memory`` is a CUDA memory-
    format knob with no XLA meaning and is intentionally absent."""
    rot_dim = cos_.shape[-1]
    t_rot, t_pass = t[..., :rot_dim], t[..., rot_dim:]
    tr = t_rot.astype(jnp.float32)
    out = (tr * cos_.astype(jnp.float32)
           + _rotate_half(tr) * sin_.astype(jnp.float32)).astype(t.dtype)
    if t_pass.shape[-1] == 0:
        return out
    return jnp.concatenate([out, t_pass], axis=-1)


def apply_rotary_pos_emb(t, freqs, interleaved: bool = False):
    """Apply RoPE to the first ``rot_dim`` channels of ``t``.

    Matches the reference semantics (fused_rope.py:19-78): channels beyond
    freqs.shape[-1] pass through; math in fp32, output keeps t.dtype.
    ``interleaved`` rotates consecutive pairs (0,1), (2,3), ... (the
    DeepSeek / GPT-J layout) instead of channel i with i + rot_dim/2;
    ``freqs`` then comes from ``rope_frequencies(..., interleaved=True)``.
    """
    rot_dim = freqs.shape[-1]
    t_rot, t_pass = t[..., :rot_dim], t[..., rot_dim:]
    f = freqs.astype(jnp.float32)
    tr = t_rot.astype(jnp.float32)
    partner = _rotate_pairs(tr) if interleaved else _rotate_half(tr)
    out = tr * jnp.cos(f) + partner * jnp.sin(f)
    out = out.astype(t.dtype)
    if t_pass.shape[-1] == 0:
        return out
    return jnp.concatenate([out, t_pass], axis=-1)
