"""Megatron-style parallel transformer layer, TPU-native.

Reference parity: the ParallelMLP / ParallelAttention / ParallelTransformerLayer
stack in apex/transformer/testing/standalone_transformer_lm.py (the reference's
canonical consumer of its TP/SP primitives), built on:
- ColumnParallelLinear / RowParallelLinear (tensor_parallel/layers.py:460,645)
- FusedScaleMaskSoftmax (functional/fused_softmax.py) → here a Pallas flash
  attention (ops/attention.py) with a fused-softmax fallback for masked paths
- FusedLayerNorm with sequence_parallel flags (transformer/layers/layer_norm.py:33)
- fused RoPE (functional/fused_rope.py) → ops/rope.py
- bias-GeLU fusion (the reference's bias_gelu_impl) → XLA epilogue fusion.

Layout: hidden states are (seq, batch, hidden) exactly like Megatron, so the
sequence-parallel scatter/gather mappings act on dim 0. All residual math can
be forced to fp32 (``fp32_residual_connection``); matmuls accumulate in fp32
on the MXU via ``preferred_element_type``.
"""

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.monitor.goodput.scopes import model_scope
from apex_tpu.ops.attention import flash_attention, latent_flash_attention
from apex_tpu.ops.layer_norm import layer_norm, rms_norm
from apex_tpu.ops.rope import apply_rotary_pos_emb, rope_frequencies
from apex_tpu.ops.softmax import fused_scale_mask_softmax
from apex_tpu.parallel.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    _tp_size,
)
from apex_tpu.parallel.mappings import copy_to_tensor_model_parallel_region
from apex_tpu.transformer.config import TransformerConfig
from apex_tpu.transformer.enums import AttnMaskType, AttnType


class Norm(nn.Module):
    """LayerNorm/RMSNorm with sequence-parallel gradient synchronization.

    Ref: transformer/layers/layer_norm.py:26-51 marks LN params
    ``sequence_parallel_enabled`` so Megatron allreduces their grads over TP
    after backward — under SP each rank's scale/bias grad is a *partial* sum
    over its sequence shard. The SPMD equivalent is routing the params
    through ``copy_to_tensor_model_parallel_region`` (identity forward,
    psum backward), which makes autodiff emit exactly that allreduce.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = x.shape[-1]
        w = self.param("scale", nn.initializers.ones_init(), (h,), cfg.params_dtype)
        sp = cfg.sequence_parallel and _tp_size(cfg.tensor_axis) > 1
        if sp:
            w = copy_to_tensor_model_parallel_region(w, cfg.tensor_axis)
        if cfg.normalization == "rmsnorm":
            return rms_norm(x, w.astype(jnp.float32), eps=cfg.layernorm_epsilon).astype(
                x.dtype
            )
        b = self.param("bias", nn.initializers.zeros_init(), (h,), cfg.params_dtype)
        if sp:
            b = copy_to_tensor_model_parallel_region(b, cfg.tensor_axis)
        return layer_norm(
            x, w.astype(jnp.float32), b.astype(jnp.float32), eps=cfg.layernorm_epsilon
        ).astype(x.dtype)


def _activate(h, activation: str):
    # computed in h's dtype on purpose: gelu/silu/relu are pointwise and
    # bf16-stable (bf16 shares f32's exponent range, and activation
    # curvature tolerates the shorter mantissa). Upcasting here would
    # materialize the (s, b, ffn) tensor — the widest activation in the
    # network — in f32, doubling its bandwidth and remat footprint for
    # no accuracy return (flagged by apex_tpu.analysis precision pass).
    if activation == "gelu":
        return jax.nn.gelu(h, approximate=True)
    if activation == "relu":
        return jax.nn.relu(h)
    if activation in ("geglu", "swiglu"):
        a, b = jnp.split(h, 2, axis=-1)
        gate = jax.nn.gelu(a, approximate=True) if activation == "geglu" else jax.nn.silu(a)
        return gate * b
    raise ValueError(f"unknown activation {activation!r}")


class ParallelMLP(nn.Module):
    """Column(h→ffn) → activation → Row(ffn→h).

    Ref: ParallelMLP in standalone_transformer_lm.py; the bias+GeLU fusion
    (reference ``bias_gelu_impl`` custom autograd fn) is an XLA epilogue here.
    Gated activations (geglu/swiglu) double the column projection width.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, hidden_states):
        cfg = self.config
        gated = cfg.activation in ("geglu", "swiglu")
        width = cfg.ffn_hidden_size * (2 if gated else 1)
        h = ColumnParallelLinear(
            output_size=width,
            gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
            axis_name=cfg.tensor_axis,
            params_dtype=cfg.params_dtype,
            use_bias=cfg.add_bias_linear,
            name="dense_h_to_4h",
        )(hidden_states)
        h = _activate(h, cfg.activation)
        return RowParallelLinear(
            output_size=cfg.hidden_size,
            input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
            axis_name=cfg.tensor_axis,
            params_dtype=cfg.params_dtype,
            use_bias=cfg.add_bias_linear,
            name="dense_4h_to_h",
        )(h)


class ShardAwareDropout(nn.Module):
    """Dropout whose mask is decorrelated across shards holding different
    slices of the same logical tensor.

    The SPMD analogue of the reference keeping distinct RNG states per
    model-parallel rank (tensor_parallel/random.py:124-236): inside
    shard_map every rank receives the same flax 'dropout' key, so without
    folding in the shard index, sequence chunks (cp) and head shards (tp)
    would draw byte-identical masks.
    """

    rate: float
    axis_names: tuple = ()

    @nn.compact
    def __call__(self, x, deterministic: bool = False):
        if deterministic or self.rate == 0.0:
            return x
        from apex_tpu.parallel.random import shard_aware_rng_key

        key = shard_aware_rng_key(self.make_rng("dropout"), self.axis_names)
        keep = jax.random.bernoulli(key, 1.0 - self.rate, x.shape)
        return jnp.where(keep, x / (1.0 - self.rate), jnp.zeros_like(x))


def _hidden_dropout_axes(cfg) -> tuple:
    """Axes over which hidden-state dropout masks must differ: tp when the
    sequence is SP-sharded, cp when context-parallel."""
    axes = ()
    if cfg.sequence_parallel:
        axes += (cfg.tensor_axis,)
    if cfg.context_parallel_mode is not None:
        axes += (cfg.context_axis,)
    return axes


class CoreAttention(nn.Module):
    """Unfused attention math for masked/dropout paths.

    Ref: CoreAttention in standalone_transformer_lm.py — baddbmm +
    FusedScaleMaskSoftmax + attention dropout + bmm. Used when flash
    attention can't apply (arbitrary padding masks, attention dropout).
    """

    config: TransformerConfig
    attn_mask_type: AttnMaskType

    @nn.compact
    def __call__(self, q, k, v, attention_mask, deterministic: bool = True):
        # q,k,v: (b, np, s, hn)
        cfg = self.config
        norm = 1.0 / math.sqrt(cfg.kv_channels)
        scale = norm
        softmax_scale = 1.0
        if cfg.apply_query_key_layer_scaling:
            # ref: layer-number scaling folded into softmax scale
            coeff = max(1, cfg.num_layers)
            scale = norm / coeff
            softmax_scale = coeff
        s = jnp.einsum("bnqd,bnkd->bnqk", q, k, preferred_element_type=jnp.float32)
        s = s * scale
        causal = self.attn_mask_type == AttnMaskType.causal
        if causal and attention_mask is not None:
            # fold the padding mask into the causal one so the fused causal
            # path still applies (ref: mask_func composition in CoreAttention)
            from apex_tpu.ops.attention import causal_mask

            future = causal_mask(s.shape[-2], s.shape[-1])
            attention_mask = jnp.logical_or(attention_mask, future)
            causal = False
        probs = fused_scale_mask_softmax(
            s, attention_mask, scale=softmax_scale, causal=causal
        )
        if cfg.attention_dropout > 0.0 and not deterministic:
            # heads are tp-sharded: masks must differ per tp rank (the
            # reference forks the model-parallel RNG around attn dropout)
            probs = ShardAwareDropout(
                rate=cfg.attention_dropout, axis_names=(cfg.tensor_axis,)
            )(probs, deterministic=deterministic)
        ctx = jnp.einsum(
            "bnqk,bnkd->bnqd",
            probs.astype(q.dtype),
            v,
            preferred_element_type=jnp.float32,
        )
        return ctx.astype(q.dtype)


class ParallelAttention(nn.Module):
    """TP multi-head attention with flash-attention core.

    Ref: ParallelAttention in standalone_transformer_lm.py — fused QKV
    ColumnParallelLinear (heads sharded over tp), core attention, Row
    output projection. Cross-attention splits q from kv like the
    reference's AttnType.cross_attn branch.
    """

    config: TransformerConfig
    attn_type: AttnType = AttnType.self_attn
    attn_mask_type: AttnMaskType = AttnMaskType.causal

    @nn.compact
    def __call__(
        self,
        hidden_states,
        attention_mask=None,
        encoder_output=None,
        rotary_pos_emb=None,
        key_padding_mask=None,
        deterministic: bool = True,
        cache_len: Optional[int] = None,
        decode_step: bool = False,
    ):
        cfg = self.config
        if decode_step and cfg.sequence_parallel:
            # one decode token cannot be sequence-sharded over tp: the step
            # runs in plain-TP layout (replicated 1-token input/output; SP
            # only moves activations, so every param is identical) while
            # PREFILL keeps full SP — its column linears gather the
            # sequence anyway, so the cache receives full-length K/V
            cfg = dataclasses.replace(cfg, sequence_parallel=False)
        s, b, _ = hidden_states.shape
        tp = _tp_size(cfg.tensor_axis)
        np_local = cfg.num_attention_heads // tp
        hn = cfg.kv_channels

        cache_active = cache_len is not None or decode_step
        if cache_active:
            # KV-cache decoding (extension: the reference has no inference
            # path). Prefill (cache_len=N): normal causal attention over the
            # prompt + rotated K/V written into "cache" variables. Step
            # (decode_step): one new token attends the cache through the
            # flash key-padding fast path. TP shards the cache with the
            # heads. Under SP, decode steps run plain-TP (see above); under
            # CP, each rank caches the positions it computed (prompt shard
            # + round-robin decode slots) and decode merges per-rank
            # partial softmax stats via cp_decode_attention.
            # CONTRACT: at most N - prompt_len decode steps after a
            # cache_len=N prefill. The index is traced, so overstepping
            # cannot raise here — the dynamic updates would clamp and
            # silently rewrite position N-1. models.generate sizes the
            # cache so this cannot happen; direct callers must too.
            if self.attn_type != AttnType.self_attn:
                raise NotImplementedError("KV cache is self-attention only")
            if attention_mask is not None or key_padding_mask is not None:
                raise NotImplementedError("KV-cache decode computes its own "
                                          "masks")
            # the KIND of cache the step is handed picks its decode branch:
            # this module's own contiguous cached_key / cached_value with a
            # scalar cache_index (models.generate, cp decode), or a serving
            # engine's paged pool (key_pool / value_pool, a block table and
            # per-lane positions: serving/kvcache.py)
            paged = decode_step and self.has_variable("cache", "key_pool")
            if (decode_step and not paged
                    and not self.has_variable("cache", "cached_key")):
                raise ValueError("decode_step before prefill: call once with "
                                 "cache_len=<total length> first")

        groups = cfg.num_query_groups or cfg.num_attention_heads
        if groups != cfg.num_attention_heads and self.attn_type != AttnType.self_attn:
            raise NotImplementedError("GQA is a self-attention feature")
        if cfg.num_attention_heads % groups != 0 or groups % tp != 0:
            raise ValueError(
                f"num_query_groups ({groups}) must divide "
                f"num_attention_heads ({cfg.num_attention_heads}) and be "
                f"divisible by tp ({tp})"
            )
        g_local = groups // tp

        if self.attn_type == AttnType.self_attn and groups != cfg.num_attention_heads:
            # GQA: separate Q and fused-KV projections (llama convention,
            # consecutive grouping — matches ops.flash_attention's
            # q_head // group kv indexing)
            q = ColumnParallelLinear(
                output_size=cfg.num_attention_heads * hn,
                gather_output=False,
                sequence_parallel_enabled=cfg.sequence_parallel,
                axis_name=cfg.tensor_axis,
                params_dtype=cfg.params_dtype,
                use_bias=cfg.add_bias_linear,
                name="query",
            )(hidden_states)
            kv = ColumnParallelLinear(
                output_size=2 * groups * hn,
                gather_output=False,
                sequence_parallel_enabled=cfg.sequence_parallel,
                axis_name=cfg.tensor_axis,
                params_dtype=cfg.params_dtype,
                use_bias=cfg.add_bias_linear,
                name="key_value",
            )(hidden_states)
            q = q.reshape(q.shape[0], b, np_local, hn)
            kv = kv.reshape(kv.shape[0], b, g_local, 2 * hn)
            k, v = jnp.split(kv, 2, axis=-1)  # (s, b, g_local, hn)
        elif self.attn_type == AttnType.self_attn:
            qkv = ColumnParallelLinear(
                output_size=3 * cfg.num_attention_heads * hn,
                gather_output=False,
                sequence_parallel_enabled=cfg.sequence_parallel,
                axis_name=cfg.tensor_axis,
                params_dtype=cfg.params_dtype,
                use_bias=cfg.add_bias_linear,
                name="query_key_value",
            )(hidden_states)
            sq = qkv.shape[0]
            qkv = qkv.reshape(sq, b, np_local, 3 * hn)
            q, k, v = jnp.split(qkv, 3, axis=-1)  # (s, b, np, hn)
        else:
            q = ColumnParallelLinear(
                output_size=cfg.num_attention_heads * hn,
                gather_output=False,
                sequence_parallel_enabled=cfg.sequence_parallel,
                axis_name=cfg.tensor_axis,
                params_dtype=cfg.params_dtype,
                use_bias=cfg.add_bias_linear,
                name="query",
            )(hidden_states)
            kv = ColumnParallelLinear(
                output_size=2 * cfg.num_attention_heads * hn,
                gather_output=False,
                # SP-sharded encoder output must be gathered for K/V too
                # (ref: standalone_transformer_lm.py:412-419)
                sequence_parallel_enabled=cfg.sequence_parallel,
                axis_name=cfg.tensor_axis,
                params_dtype=cfg.params_dtype,
                use_bias=cfg.add_bias_linear,
                name="key_value",
            )(encoder_output)
            q = q.reshape(q.shape[0], b, np_local, hn)
            kv = kv.reshape(kv.shape[0], b, np_local, 2 * hn)
            k, v = jnp.split(kv, 2, axis=-1)

        cp = (
            _tp_size(cfg.context_axis) if cfg.context_parallel_mode is not None else 1
        )

        cache_index = None
        if decode_step:
            cache_index = self.get_variable("cache", "cache_index")

        if rotary_pos_emb is not None:
            q_pos_emb, k_pos_emb = rotary_pos_emb
            if cp > 1 and not decode_step:
                # sequence is cp-sharded: slice this rank's chunk out of the
                # GLOBAL rotary table so positions stay absolute (a decode
                # token's position is global — cache_index — not per-rank)
                def _local_chunk(emb, s_local):
                    if emb.shape[0] == s_local:
                        return emb
                    r = jax.lax.axis_index(cfg.context_axis)
                    return jax.lax.dynamic_slice_in_dim(
                        emb, r * s_local, s_local, 0
                    )

                q_pos_emb = _local_chunk(q_pos_emb, q.shape[0])
                k_pos_emb = _local_chunk(k_pos_emb, k.shape[0])
            if cache_active and q_pos_emb.shape[0] != q.shape[0]:
                # cache mode passes the FULL-length table; this call covers
                # absolute positions [pos0, pos0 + sq).  sq comes from q,
                # not the layer input: under SP the column linear has
                # already gathered the sequence, so q is s_global long
                if decode_step and paged:
                    # every lane's token at its own position: (1, b, 1, rot)
                    q_pos_emb, k_pos_emb = (
                        jnp.take(emb[:, 0, 0], cache_index, axis=0)[
                            None, :, None]
                        for emb in (q_pos_emb, k_pos_emb))
                else:
                    pos0 = cache_index if decode_step else 0
                    q_pos_emb = jax.lax.dynamic_slice_in_dim(
                        q_pos_emb, pos0, q.shape[0], 0)
                    k_pos_emb = jax.lax.dynamic_slice_in_dim(
                        k_pos_emb, pos0, k.shape[0], 0)
            q = apply_rotary_pos_emb(q, q_pos_emb, cfg.rotary_interleaved)
            k = apply_rotary_pos_emb(k, k_pos_emb, cfg.rotary_interleaved)

        # (s, b, np, hn) -> (b, np, s, hn)
        qb = jnp.transpose(q, (1, 2, 0, 3))
        kb = jnp.transpose(k, (1, 2, 0, 3))
        vb = jnp.transpose(v, (1, 2, 0, 3))

        if cache_active and paged:
            ctx = self._paged_decode(q, k, v, cache_index)
        elif cache_active:
            h_kv_local = kb.shape[1]
            # Under CP each rank caches ONLY the positions it computed:
            # its contiguous prompt shard in slots [0, prompt_local), then
            # decode tokens round-robin (token t -> rank t % cp, slot
            # prompt_local + t // cp).  Slot -> global-position mapping is
            # reconstructed from (rank, prompt_local) at decode time, so
            # no cross-rank redistribution ever happens.  cache_index
            # stays GLOBAL (identical on all ranks) — rotary tables and
            # validity masks key off absolute positions.
            if cp > 1:
                if cache_len is not None and cache_len % cp:
                    raise ValueError(
                        f"cache_len ({cache_len}) must divide by cp ({cp})"
                    )
                slots = (cache_len or 0) // cp
            else:
                slots = cache_len or 0
            ck = self.variable(
                "cache", "cached_key", jnp.zeros,
                (b, h_kv_local, slots, hn), kb.dtype,
            )
            cv = self.variable(
                "cache", "cached_value", jnp.zeros,
                (b, h_kv_local, slots, hn), vb.dtype,
            )
            ci = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            if cp > 1:
                pl = self.variable(
                    "cache", "prompt_len_local",
                    lambda: jnp.zeros((), jnp.int32)
                )
            if decode_step:
                if s != 1:
                    raise NotImplementedError(
                        "decode_step appends one token at a time; use a "
                        "prefill call (cache_len=...) for multi-token blocks"
                    )
                idx = cache_index  # global position of this token
                # One slot/mask implementation for both layouts: with
                # cp == 1 the round-robin map degenerates to slot = idx
                # and gpos = j (p_loc = prompt length, r = owner = 0).
                if cp > 1:
                    r = jax.lax.axis_index(cfg.context_axis)
                    p_loc = pl.value
                    d_cnt = idx - p_loc * cp  # decode tokens written so far
                    slot = p_loc + d_cnt // cp
                    write_here = r == d_cnt % cp
                else:
                    slot = idx
                    write_here = None  # every (i.e. the only) rank writes
                new_k = jax.lax.dynamic_update_slice(
                    ck.value, kb.astype(ck.value.dtype), (0, 0, slot, 0)
                )
                new_v = jax.lax.dynamic_update_slice(
                    cv.value, vb.astype(cv.value.dtype), (0, 0, slot, 0)
                )
                if write_here is None:
                    ck.value, cv.value = new_k, new_v
                else:
                    ck.value = jnp.where(write_here, new_k, ck.value)
                    cv.value = jnp.where(write_here, new_v, cv.value)
                ci.value = idx + 1
                j = jnp.arange(ck.value.shape[2])
                gpos = j if cp == 1 else jnp.where(
                    j < p_loc,
                    r * p_loc + j,
                    p_loc * cp + (j - p_loc) * cp + r,
                )
                # pad out the unwritten future; the sliding window
                # additionally drops keys behind the band (mistral decode)
                padded = gpos > idx
                if cfg.attention_window is not None:
                    padded = jnp.logical_or(
                        padded, gpos <= idx - cfg.attention_window
                    )
                padded = jnp.broadcast_to(padded[None, :], (b, j.size))
                if cp > 1:
                    from apex_tpu.parallel.ring_attention import (
                        cp_decode_attention,
                    )

                    ctx = cp_decode_attention(
                        qb, ck.value, cv.value, padded,
                        axis_name=cfg.context_axis,
                    )
                else:
                    ctx = flash_attention(
                        qb, ck.value, cv.value, causal=False,
                        key_padding_mask=padded, impl=cfg.attention_impl,
                    )
            else:
                # prefill: record the (rotated) prompt K/V, then fall
                # through to the normal attention paths below.  kb, not the
                # layer input, carries the cached length: under SP the
                # column linear has gathered the full sequence; under CP
                # this is the rank's contiguous shard (ring/ulysses run on
                # the default non-zigzag layout — zigzag prefill would
                # scatter positions the slot map above can't reconstruct)
                s_kv = kb.shape[2]
                assert s_kv <= slots, (
                    f"prompt ({s_kv}{' per cp rank' if cp > 1 else ''}) "
                    f"exceeds cache ({slots})"
                )
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, kb.astype(ck.value.dtype), (0, 0, 0, 0)
                )
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, vb.astype(cv.value.dtype), (0, 0, 0, 0)
                )
                ci.value = jnp.asarray(s_kv * cp, jnp.int32)
                if cp > 1:
                    pl.value = jnp.asarray(s_kv, jnp.int32)

        causal = self.attn_mask_type == AttnMaskType.causal
        # apply_query_key_layer_scaling cancels exactly (scores*norm/coeff
        # then softmax_scale=coeff) in the always-fp32 softmax, so the flash
        # path with scale=norm is semantically identical — no fallback needed.
        use_flash = attention_mask is None and (
            cfg.attention_dropout == 0.0 or deterministic
        )
        if key_padding_mask is not None and not use_flash:
            # fold the (b, sk) padding row into the dense mask for the
            # unfused CoreAttention path (True = masked out)
            kp = key_padding_mask[:, None, None, :]
            attention_mask = (
                kp if attention_mask is None
                else jnp.logical_or(attention_mask, kp)
            )
            key_padding_mask = None
        if decode_step:
            pass  # ctx computed against the cache above
        elif cp > 1:
            if not use_flash:
                raise NotImplementedError(
                    "context parallelism requires the flash path: no "
                    "dense attention_mask and no attention dropout (like "
                    "the reference's fused paths); GQA and key-padding "
                    "masks are supported"
                )
            from apex_tpu.parallel.ring_attention import (
                ring_attention,
                ulysses_attention,
            )

            # key_padding_mask here is the LOCAL (b, s_local) shard — the
            # layer runs inside shard_map with sequence-sharded inputs, so
            # the mask arrives sharded exactly like the keys it pads
            win = cfg.attention_window if causal else None
            if cfg.context_parallel_mode == "ring":
                ctx = ring_attention(
                    qb, kb, vb, axis_name=cfg.context_axis, causal=causal,
                    window=win, key_padding_mask=key_padding_mask,
                )
            else:
                ctx = ulysses_attention(
                    qb,
                    kb,
                    vb,
                    axis_name=cfg.context_axis,
                    causal=causal,
                    window=win,
                    attn_fn=functools.partial(
                        flash_attention, impl=cfg.attention_impl
                    ),
                    key_padding_mask=key_padding_mask,
                )
        elif use_flash:
            ctx = flash_attention(
                qb, kb, vb, causal=causal, key_padding_mask=key_padding_mask,
                window=cfg.attention_window if causal else None,
                impl=cfg.attention_impl,
            )
        else:
            if kb.shape[1] != qb.shape[1]:  # GQA through the unfused path
                rep = qb.shape[1] // kb.shape[1]
                kb = jnp.repeat(kb, rep, axis=1)
                vb = jnp.repeat(vb, rep, axis=1)
            if cfg.attention_window is not None and causal:
                # fold the band's lower edge into the dense mask; the causal
                # upper edge stays with CoreAttention's own mask handling
                from apex_tpu.ops.attention import window_mask

                below = window_mask(
                    qb.shape[2], kb.shape[2], cfg.attention_window
                )[None, None]
                attention_mask = (
                    below if attention_mask is None
                    else jnp.logical_or(attention_mask, below)
                )
            ctx = CoreAttention(
                config=cfg, attn_mask_type=self.attn_mask_type, name="core_attention"
            )(qb, kb, vb, attention_mask, deterministic=deterministic)

        # (b, np, s, hn) -> (s, b, np*hn)
        ctx = jnp.transpose(ctx, (2, 0, 1, 3)).reshape(ctx.shape[2], b, np_local * hn)
        out = RowParallelLinear(
            output_size=cfg.hidden_size,
            input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
            axis_name=cfg.tensor_axis,
            params_dtype=cfg.params_dtype,
            use_bias=cfg.add_bias_linear,
            name="dense",
        )(ctx)
        return out

    def _paged_decode(self, q, k, v, positions):
        """A decode step over a paged KV pool: each lane's new key and value
        ((1, lanes, h_kv, hn), rotated) go into slot ``positions % bs`` of
        pool block ``table[positions // bs]``, then every lane's query
        attends keys [0, position] where they lie
        (``ops.paged_decode_attention``). A lane with no block under its
        position (an out-of-range entry: the sentinel of an idle lane) holds
        nothing: its write is dropped and it attends no key (length 0, a
        zero context). Returns (lanes, np, 1, hn)."""
        from apex_tpu.ops.attention import paged_decode_attention

        cfg = self.config
        if q.shape[0] != 1:
            raise NotImplementedError(
                "decode_step appends one token at a time; use a prefill "
                "call (cache_len=...) for multi-token blocks")
        if cfg.context_parallel_mode is not None:
            raise NotImplementedError(
                "a paged cache holds whole sequences: no context parallelism")
        table = self.get_variable("cache", "block_table")
        pools = [self.get_variable("cache", name)
                 for name in ("key_pool", "value_pool")]
        lanes = q.shape[1]
        nb, bs, _ = pools[0].shape
        blk = table[jnp.arange(lanes), positions // bs]
        live = jnp.logical_and(blk >= 0, blk < nb)
        blk = jnp.where(live, blk, nb)  # out of range: the write drops
        for i, (name, new) in enumerate((("key_pool", k), ("value_pool", v))):
            pools[i] = pools[i].at[blk, positions % bs].set(
                new[0].reshape(lanes, -1).astype(pools[i].dtype), mode="drop")
            self.put_variable("cache", name, pools[i])
        ctx = paged_decode_attention(
            q[0], *pools, table, jnp.where(live, positions + 1, 0),
            scale=1.0 / math.sqrt(cfg.kv_channels),
            window=cfg.attention_window, impl=cfg.attention_impl)
        return ctx[:, :, None, :]


class _HeadColumnsDense(nn.Module):
    """A bias-free ``nn.Dense`` whose output columns are ``heads`` groups
    of ``sum(widths)``, returned as one (..., heads * width) output for
    each of ``widths``: the same parameter as the Dense it stands for
    (``kernel``, (in, heads * sum(widths))), read as its column sets. The
    WEIGHT is sliced, where the cast to the compute dtype reads it anyway,
    so the activation is never sliced."""

    heads: int
    widths: tuple
    dtype: Any
    param_dtype: Any
    kernel_init: Callable

    @nn.compact
    def __call__(self, x):
        rows, per_head = x.shape[-1], sum(self.widths)
        kernel = self.param("kernel", self.kernel_init,
                            (rows, self.heads * per_head), self.param_dtype)
        kernel = kernel.astype(self.dtype).reshape(rows, self.heads, per_head)
        outs, lo = [], 0
        for w in self.widths:
            outs.append(jnp.dot(
                x, kernel[:, :, lo:lo + w].reshape(rows, self.heads * w)))
            lo += w
        return outs


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3 MLA) in its training
    form: queries and keys/values come through low-rank bottlenecks
    (``q_lora_rank``, ``kv_lora_rank``, each normed), every head has a
    no-rope part of its own and a rope part whose key side ALL heads share,
    and the values are narrower than the keys. The flash kernels read the
    projections' outputs as they are written (batch-major rows, a head a
    column range: ``ops.attention.latent_flash_attention``) and write the
    context where ``o_proj`` reads it: nothing of tokens x heads x d size
    is sliced, concatenated, broadcast or transposed. No absorbed form, no
    cache (serving it needs a latent paged cache: ROADMAP R8)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None,
                 encoder_output=None, rotary_pos_emb=None,
                 key_padding_mask=None, deterministic: bool = True):
        cfg = self.config
        if attention_mask is not None or encoder_output is not None:
            raise NotImplementedError(
                "latent attention is causal self-attention on the flash "
                "path: no dense mask, no cross attention")
        if _tp_size(cfg.tensor_axis) > 1 or cfg.context_parallel_mode:
            raise NotImplementedError("latent attention under tp or cp")
        heads = cfg.num_attention_heads
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        init = dict(dtype=hidden_states.dtype, param_dtype=cfg.params_dtype,
                    kernel_init=nn.initializers.normal(stddev=0.02))
        dense = functools.partial(nn.Dense, use_bias=False, **init)
        # (s, b, h) -> (b, s, h): the kernels' row blocks are runs of one
        # batch row's tokens
        x = jnp.swapaxes(hidden_states, 0, 1)
        with model_scope("mla_project"):
            c_q = Norm(config=cfg, name="q_a_layernorm")(
                dense(cfg.q_lora_rank, name="q_a_proj")(x))
            q_nope, q_rope = _HeadColumnsDense(
                heads=heads, widths=(nope, rope), name="q_b_proj", **init)(c_q)
            kv_a = dense(cfg.kv_lora_rank + rope, name="kv_a_proj")(x)
            c_kv = Norm(config=cfg, name="kv_a_layernorm")(
                kv_a[..., : cfg.kv_lora_rank])
            kv = dense(heads * (nope + dv), name="kv_b_proj")(c_kv)
        ctx = latent_flash_attention(
            q_nope, q_rope, kv, kv_a[..., cfg.kv_lora_rank:],
            rotary_pos_emb[0], heads=heads,
            interleaved=cfg.rotary_interleaved,
            scale=1.0 / math.sqrt(nope + rope),
            key_padding_mask=key_padding_mask, impl=cfg.attention_impl)
        with model_scope("mla_project"):
            out = dense(cfg.hidden_size, name="o_proj")(ctx)
        return jnp.swapaxes(out, 0, 1)


class ParallelTransformerLayer(nn.Module):
    """Pre-LN transformer block (ref: ParallelTransformerLayer in
    standalone_transformer_lm.py): LN → attn → residual → LN → MLP → residual,
    with optional post-LN residual taps and fp32 residual stream."""

    config: TransformerConfig
    attn_mask_type: AttnMaskType = AttnMaskType.causal
    has_cross_attention: bool = False
    #: what this layer is: (attention kind, MLP kind), as
    #: ``TransformerConfig.layer_kinds`` gives them; None = layer 0's
    kinds: Optional[tuple] = None

    @nn.compact
    def __call__(
        self,
        hidden_states,
        attention_mask=None,
        encoder_output=None,
        enc_dec_attn_mask=None,
        rotary_pos_emb=None,
        key_padding_mask=None,
        deterministic: bool = True,
        cache_len: Optional[int] = None,
        decode_step: bool = False,
    ):
        cfg = self.config
        if decode_step and cfg.sequence_parallel:
            # decode steps run plain-TP (see ParallelAttention): a single
            # token cannot be sequence-sharded, so the MLP's column/row
            # linears must not gather/scatter a sequence axis either
            cfg = dataclasses.replace(cfg, sequence_parallel=False)
        rdtype = jnp.float32 if cfg.fp32_residual_connection else hidden_states.dtype
        cache_active = cache_len is not None or decode_step
        attn_kind, mlp_kind = self.kinds or cfg.layer_kinds(0)
        latent = attn_kind == "latent"
        if latent and (cache_active or self.has_cross_attention):
            raise NotImplementedError(
                "latent attention has no cache and no cross attention")

        ln_out = Norm(config=cfg, name="input_layernorm")(hidden_states)
        attn_cls = LatentAttention if latent else ParallelAttention
        if cfg.recompute_granularity == "selective" and not cache_active:
            # recompute only the attention block in backward (ref: Megatron
            # --recompute-granularity selective; core-attention checkpoint).
            # arg 0 is the module scope; ``deterministic`` (arg 6) is static.
            # (decode has no backward — remat would only re-trace the cache
            # mutation, so it is skipped in cache mode)
            attn_cls = nn.remat(
                attn_cls, static_argnums=(6,), prevent_cse=False
            )
        attn_out = attn_cls(
            config=cfg,
            **({} if latent else dict(
                attn_type=AttnType.self_attn,
                attn_mask_type=self.attn_mask_type)),
            name="self_attention",
        )(
            ln_out,
            attention_mask,
            None,
            rotary_pos_emb,
            key_padding_mask,
            deterministic,
            **(
                {"cache_len": cache_len, "decode_step": decode_step}
                if cache_active
                else {}
            ),
        )
        residual = (
            ln_out if cfg.apply_residual_connection_post_layernorm else hidden_states
        )
        if cfg.hidden_dropout > 0.0 and not deterministic:
            attn_out = ShardAwareDropout(
                rate=cfg.hidden_dropout, axis_names=_hidden_dropout_axes(cfg)
            )(attn_out, deterministic=deterministic)
        h = (residual.astype(rdtype) + attn_out.astype(rdtype)).astype(
            hidden_states.dtype
        )

        if self.has_cross_attention:
            ln_x = Norm(config=cfg, name="post_inter_attention_layernorm_pre")(h)
            x_out = ParallelAttention(
                config=cfg,
                attn_type=AttnType.cross_attn,
                attn_mask_type=AttnMaskType.padding,
                name="inter_attention",
            )(
                ln_x,
                attention_mask=enc_dec_attn_mask,
                encoder_output=encoder_output,
                deterministic=deterministic,
            )
            residual = ln_x if cfg.apply_residual_connection_post_layernorm else h
            h = (residual.astype(rdtype) + x_out.astype(rdtype)).astype(
                hidden_states.dtype
            )

        ln2 = Norm(config=cfg, name="post_attention_layernorm")(h)
        if mlp_kind == "experts":
            from apex_tpu.transformer.moe import MoEMLP

            s_, b_, h_ = ln2.shape
            mlp_out, moe_aux = MoEMLP(
                config=cfg,
                num_experts=cfg.num_moe_experts,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                expert_axis=cfg.moe_expert_axis,
                router=cfg.moe_router,
                norm_topk_prob=cfg.moe_norm_topk_prob,
                routed_scaling_factor=cfg.moe_routed_scaling_factor,
                gated=cfg.moe_gated_experts,
                **({"activation": jax.nn.silu} if cfg.moe_gated_experts
                   else {}),
                ffn_hidden_size=cfg.moe_ffn_hidden_size,
                shared_experts=cfg.moe_shared_experts,
                experts_held=cfg.moe_experts_held,
                first_expert=cfg.moe_first_expert,
                impl=cfg.moe_impl,
                name="mlp",
            )(ln2.reshape(s_ * b_, h_))
            mlp_out = mlp_out.reshape(s_, b_, h_)
            # surface the aux loss: readers pull it via
            # mutable=['intermediates'] and add moe_aux_loss_coeff * mean
            self.sow("intermediates", "moe_aux_loss", moe_aux)
        else:
            mlp_out = ParallelMLP(config=cfg, name="mlp")(ln2)
        residual = ln2 if cfg.apply_residual_connection_post_layernorm else h
        if cfg.hidden_dropout > 0.0 and not deterministic:
            mlp_out = ShardAwareDropout(
                rate=cfg.hidden_dropout, axis_names=_hidden_dropout_axes(cfg)
            )(mlp_out, deterministic=deterministic)
        out = (residual.astype(rdtype) + mlp_out.astype(rdtype)).astype(
            hidden_states.dtype
        )
        if cfg.collect_layer_metrics:
            # per-layer activation-scale tap (registered in monitor/taps.py;
            # read via monitor.taps_from_intermediates): fp32 RMS of the
            # block output, the series that localizes a divergence to a
            # depth before it reaches the loss
            self.sow(
                "intermediates",
                "layer_out_rms",
                jnp.sqrt(jnp.mean(jnp.square(out.astype(jnp.float32)))),
            )
        return out


class ParallelTransformer(nn.Module):
    """Stack of layers + final LN, with activation recompute.

    Ref: ParallelTransformer in standalone_transformer_lm.py; activation
    checkpointing (tensor_parallel/random.py:237 CheckpointFunction) maps to
    ``jax.checkpoint`` (``nn.remat``) around each layer when
    ``recompute_granularity == "full"``. ``num_layers`` here is the LOCAL
    stage depth — pipeline stages instantiate their own slice (ref:
    build_model virtual chunks, schedules/common.py:30).
    """

    config: TransformerConfig
    num_layers: Optional[int] = None
    post_layer_norm: bool = True
    attn_mask_type: AttnMaskType = AttnMaskType.causal
    #: also return the last layer's output before the final norm (what a
    #: multi-token-prediction block continues from): (normed, before)
    also_pre_norm: bool = False

    @nn.compact
    def __call__(
        self,
        hidden_states,
        attention_mask=None,
        rotary_pos_emb=None,
        key_padding_mask=None,
        deterministic: bool = True,
        cache_len: Optional[int] = None,
        decode_step: bool = False,
    ):
        cfg = self.config
        n = self.num_layers if self.num_layers is not None else cfg.num_layers
        cache_active = cache_len is not None or decode_step
        layer_cls = ParallelTransformerLayer
        if cfg.recompute_granularity == "full" and not cache_active:
            # arg 0 is the module scope; ``deterministic`` (arg 7) is static
            # (no backward in decode — see ParallelTransformerLayer)
            layer_cls = nn.remat(
                ParallelTransformerLayer,
                static_argnums=(7,),
                prevent_cse=False,
            )
        for i in range(n):
            hidden_states = layer_cls(
                config=cfg, attn_mask_type=self.attn_mask_type,
                kinds=cfg.layer_kinds(i), name=f"layer_{i}"
            )(
                hidden_states,
                attention_mask,
                None,
                None,
                rotary_pos_emb,
                key_padding_mask,
                deterministic,
                **(
                    {"cache_len": cache_len, "decode_step": decode_step}
                    if cache_active
                    else {}
                ),
            )
        before = hidden_states
        if self.post_layer_norm:
            hidden_states = Norm(config=cfg, name="final_layernorm")(hidden_states)
        return (hidden_states, before) if self.also_pre_norm else hidden_states


class MultiTokenPrediction(nn.Module):
    """DeepSeek-V3's multi-token-prediction module: from the trunk's last
    hidden state at position i (before the final norm) and the embedding
    of token i+1, one more layer of the stack's last kind, with norms of
    its own, whose output the TRUNK's head reads as the logits of token
    i+2: ``h' = W_eh [norm(h_i) | norm(emb(t_{i+1}))]``, the layer, a final
    norm. Embedding and head are the caller's (``models.GPTModel``)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, hidden_states, next_embeddings, rotary_pos_emb=None,
                 key_padding_mask=None, deterministic: bool = True):
        cfg = self.config
        joined = jnp.concatenate(
            [Norm(config=cfg, name="hnorm")(hidden_states),
             Norm(config=cfg, name="enorm")(next_embeddings)], axis=-1)
        x = nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=hidden_states.dtype,
            param_dtype=cfg.params_dtype,
            kernel_init=nn.initializers.normal(stddev=0.02), name="eh_proj",
        )(joined)
        x = ParallelTransformerLayer(
            config=cfg, kinds=cfg.layer_kinds(cfg.num_layers), name="layer",
        )(x, None, None, None, rotary_pos_emb, key_padding_mask,
          deterministic)
        return Norm(config=cfg, name="final_layernorm")(x)


def rotary_embedding_for(config: TransformerConfig, seq_len: int, dtype=jnp.float32):
    """Precompute (q_freqs, k_freqs) for the attention modules' rotary
    path: over ``kv_channels * rotary_percent`` channels, or over latent
    attention's ``qk_rope_head_dim``."""
    rot_dim = (config.qk_rope_head_dim
               if config.layer_kinds(0)[0] == "latent"
               else int(config.kv_channels * config.rotary_percent))
    f = rope_frequencies(rot_dim, seq_len, base=config.rotary_base,
                         dtype=dtype, interleaved=config.rotary_interleaved)
    return f, f
