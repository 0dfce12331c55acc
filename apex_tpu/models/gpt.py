"""GPT language model.

Reference parity: apex/transformer/testing/standalone_gpt.py (gpt_model
over TransformerLanguageModel, standalone_transformer_lm.py) — vocab-parallel
embedding + learned/rotary positions, causal ParallelTransformer, tied
embedding logits, vocab-parallel cross entropy. ``pre_process``/``post_process``
mirror the pipeline-stage flags of build_model (schedules/common.py:83-108).

Layout: tokens are (batch, seq); hidden states run (seq, batch, hidden)
through the stack (Megatron layout, so sequence-parallel mappings act on
dim 0); loss is per-token (batch, seq) fp32.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.parallel.cross_entropy import vocab_parallel_cross_entropy
from apex_tpu.parallel.layers import (
    ColumnParallelLinear,
    VocabParallelEmbedding,
    _tp_size,
)
from apex_tpu.parallel.mappings import (
    gather_from_sequence_parallel_region,
    scatter_to_sequence_parallel_region,
)
from apex_tpu.transformer.config import TransformerConfig
from apex_tpu.transformer.enums import AttnMaskType
from apex_tpu.monitor.goodput.scopes import model_scope
from apex_tpu.transformer.layer import (
    MultiTokenPrediction,
    ParallelTransformer,
    rotary_embedding_for,
)


class Embedding(nn.Module):
    """Word + learned-position (+tokentype) embeddings with dropout.

    Ref: Embedding in standalone_transformer_lm.py — VocabParallelEmbedding
    plus a replicated position table; with sequence parallelism the output is
    scattered along the sequence dim (mappings.py:213).
    """

    config: TransformerConfig
    num_tokentypes: int = 0

    def setup(self):
        cfg = self.config
        self.word_embeddings = VocabParallelEmbedding(
            num_embeddings=cfg.vocab_size,
            embedding_dim=cfg.hidden_size,
            axis_name=cfg.tensor_axis,
            params_dtype=cfg.params_dtype,
            # Megatron init_method_normal(init_method_std=0.02) — the
            # reference's testing/arguments.py default; N(0,1) blows up the
            # tied-logit scale (std ~ sqrt(hidden))
            embedding_init=nn.initializers.normal(stddev=0.02),
            name="word_embeddings",
        )
        if cfg.position_embedding_type == "learned":
            self.position_embeddings = self.param(
                "position_embeddings",
                nn.initializers.normal(stddev=0.02),
                (cfg.max_position_embeddings, cfg.hidden_size),
                cfg.params_dtype,
            )
        if self.num_tokentypes > 0:
            self.tokentype_embeddings = self.param(
                "tokentype_embeddings",
                nn.initializers.normal(stddev=0.02),
                (self.num_tokentypes, cfg.hidden_size),
                cfg.params_dtype,
            )
        # setup-based module: submodules must be declared here, not inline.
        # Dropout runs BEFORE the SP scatter (full-sequence mask, identical
        # on all tp ranks) but tokens are already cp-sharded — fold cp.
        from apex_tpu.transformer.layer import ShardAwareDropout

        cp_axes = (cfg.context_axis,) if cfg.context_parallel_mode else ()
        self.dropout = ShardAwareDropout(rate=cfg.hidden_dropout, axis_names=cp_axes)

    def __call__(self, tokens, position_ids=None, tokentype_ids=None,
                 deterministic: bool = True, decode_step: bool = False):
        # decode_step: a replicated single token — skip the SP scatter (one
        # token cannot be sequence-sharded; see transformer/layer.py's
        # plain-TP decode layout)
        cfg = self.config
        h = self.word_embeddings(tokens)  # (b, s, h)
        if cfg.position_embedding_type == "learned":
            if position_ids is None:
                position_ids = jnp.arange(tokens.shape[1])[None, :]
                if cfg.context_parallel_mode is not None:
                    # cp-sharded sequence: local chunk r holds global
                    # positions r*s_local.. — offset by the cp rank (same
                    # fix as the rotary-table slice in transformer/layer.py)
                    cp = _tp_size(cfg.context_axis)
                    if cp > 1:
                        rank = jax.lax.axis_index(cfg.context_axis)
                        position_ids = position_ids + rank * tokens.shape[1]
            h = h + jnp.take(self.position_embeddings, position_ids, axis=0)
        if tokentype_ids is not None:
            if self.num_tokentypes <= 0:
                raise ValueError(
                    "tokentype_ids passed but num_tokentypes == 0 "
                    "(ref: Megatron Embedding raises on this mismatch)"
                )
            h = h + jnp.take(self.tokentype_embeddings, tokentype_ids, axis=0)
        elif self.num_tokentypes > 0:
            raise ValueError(
                "num_tokentypes > 0 but no tokentype_ids passed — the "
                "tokentype table would silently train as dead weight"
            )
        h = jnp.transpose(h, (1, 0, 2))  # (s, b, h)
        h = h.astype(cfg.compute_dtype)
        if cfg.hidden_dropout > 0.0:
            h = self.dropout(h, deterministic=deterministic)
        if (cfg.sequence_parallel and _tp_size(cfg.tensor_axis) > 1
                and not decode_step):
            h = scatter_to_sequence_parallel_region(h, cfg.tensor_axis)
        return h


class GPTModel(nn.Module):
    """Causal LM over the parallel transformer stack.

    ``num_layers`` overrides the stage-local depth for pipeline chunks;
    when ``post_process`` and labels are given, returns per-token CE losses
    (ref: post_language_model_processing in standalone_gpt.py), else logits
    (vocab-sharded over tp) or, for intermediate stages, hidden states.

    With ``config.mtp_num_layers`` (1 is what exists) and labels, returns
    ``(losses, mtp_losses)``: the second from the multi-token-prediction
    block, which reads the trunk's last hidden state and the embedding of
    the NEXT token (``labels``) through the trunk's own embedding and head,
    and predicts the token after it; its last position has no target and
    reads 0 (``gpt_mtp_loss_fn`` takes the mean over the others).
    """

    config: TransformerConfig
    pre_process: bool = True
    post_process: bool = True
    num_layers: Optional[int] = None

    def setup(self):
        cfg = self.config
        if self.pre_process or (
            self.post_process and cfg.share_embeddings_and_output_weights
        ):
            self.embedding = Embedding(config=cfg, name="embedding")
        if self.post_process and not cfg.share_embeddings_and_output_weights:
            # untied output head: vocab-parallel projection (ref: Megatron
            # untie_embeddings_and_output_weights path in parallel_lm_logits)
            self.output_layer = ColumnParallelLinear(
                output_size=cfg.vocab_size,
                use_bias=False,
                axis_name=cfg.tensor_axis,
                params_dtype=cfg.params_dtype,
                kernel_init=nn.initializers.normal(stddev=0.02),
                # the layer's own SP gather has a reduce-scatter backward —
                # half the comm of a manual gather + copy_to composition
                sequence_parallel_enabled=cfg.sequence_parallel,
                # fp32 logits for the vocab-parallel CE, like the tied path
                output_dtype=jnp.float32,
                name="output_layer",
            )
        self.with_mtp = bool(cfg.mtp_num_layers) and self.post_process
        if cfg.mtp_num_layers > 1 or (self.with_mtp and not self.pre_process):
            raise NotImplementedError(
                "one multi-token-prediction block, on a model that holds "
                "its own embedding")
        self.transformer = ParallelTransformer(
            config=cfg,
            num_layers=self.num_layers,
            post_layer_norm=self.post_process,
            attn_mask_type=AttnMaskType.causal,
            also_pre_norm=self.with_mtp,
            name="transformer",
        )
        if self.with_mtp:
            self.mtp = MultiTokenPrediction(config=cfg, name="mtp")

    def __call__(
        self,
        tokens,
        position_ids=None,
        attention_mask=None,
        key_padding_mask=None,
        labels=None,
        loss_mask=None,
        deterministic: bool = True,
        cache_len=None,
        decode_step: bool = False,
    ):
        # key_padding_mask: (b, s) bool, True = padded-out key; stays on the
        # attention fast paths (flash kernel, ring/Ulysses CP — under cp>1
        # pass the LOCAL sequence shard, sharded exactly like tokens)
        cfg = self.config
        cache_active = cache_len is not None or decode_step
        if self.pre_process:
            h = self.embedding(tokens, position_ids,
                               deterministic=deterministic,
                               decode_step=decode_step)
        else:
            h = tokens  # already (s_local, b, h) hidden states from prev stage

        rotary = None
        if cfg.position_embedding_type == "rope":
            seq = h.shape[0]
            if cfg.sequence_parallel and _tp_size(cfg.tensor_axis) > 1:
                seq = seq * _tp_size(cfg.tensor_axis)
            if cfg.context_parallel_mode is not None:
                # cp-sharded sequence: build the GLOBAL table; attention
                # slices each rank's chunk (transformer/layer.py)
                seq = seq * _tp_size(cfg.context_axis)
            if cache_active:
                # KV-cache decoding: the full-length table; attention slices
                # each call's absolute positions (prefill [0, s), step
                # [cache_index, cache_index+1))
                seq = cache_len if cache_len is not None else (
                    cfg.max_position_embeddings
                )
            rotary = rotary_embedding_for(cfg, seq)

        h = self.transformer(
            h,
            attention_mask=attention_mask,
            key_padding_mask=key_padding_mask,
            rotary_pos_emb=rotary,
            deterministic=deterministic,
            **(
                {"cache_len": cache_len, "decode_step": decode_step}
                if cache_active
                else {}
            ),
        )
        if not self.post_process:
            return h
        if self.with_mtp:
            h, trunk_out = h
        losses = self._head(h, labels, loss_mask, decode_step)
        if not self.with_mtp or labels is None:
            return losses
        with model_scope("mtp"):
            # position i: the trunk's state there and token i+1 (its label)
            # predict token i+2 (the next position's label)
            nxt = jnp.transpose(self.embedding.word_embeddings(labels),
                                (1, 0, 2)).astype(cfg.compute_dtype)
            h2 = self.mtp(trunk_out, nxt, rotary, key_padding_mask,
                          deterministic)
            targets = jnp.roll(labels, -1, axis=1)
            has_target = jnp.arange(labels.shape[1]) < labels.shape[1] - 1
            mask = has_target[None, :].astype(jnp.float32)
            if loss_mask is not None:
                mask = mask * jnp.roll(loss_mask, -1, axis=1)
            return losses, self._head(h2, targets, mask, decode_step)

    def _head(self, h, labels, loss_mask, decode_step):
        """Final hidden states (s, b, h) -> logits, or per-token losses
        when ``labels`` are given."""
        cfg = self.config
        tied = cfg.share_embeddings_and_output_weights
        # decode steps carry a replicated single token — nothing is
        # sequence-sharded, so the SP head gather must not run
        sp_gathered = (cfg.sequence_parallel and _tp_size(cfg.tensor_axis) > 1
                       and not decode_step)
        if tied:
            if sp_gathered:
                # to_model_parallel=True — attend(parallel_input=True) leaves
                # dh partial per tp rank and the gather backward is a single
                # reduce-scatter (the reference's
                # tensor_parallel_output_grad=True path)
                h = gather_from_sequence_parallel_region(
                    h, cfg.tensor_axis, to_model_parallel=True
                )
            logits = self.embedding.word_embeddings.attend(
                h, parallel_input=sp_gathered
            )  # (s, b, v/tp) fp32
        else:
            # the layer performs the SP gather itself (reduce-scatter
            # backward) and emits fp32 logits
            logits = self.output_layer(
                h,
                **({"sequence_parallel_override": False}
                   if decode_step else {}),
            )
        logits = jnp.transpose(logits, (1, 0, 2))  # (b, s, v/tp)
        if labels is None:
            return logits
        losses = vocab_parallel_cross_entropy(
            logits, labels, axis_name=cfg.tensor_axis
        )
        if loss_mask is not None:
            losses = losses * loss_mask
        return losses


def gpt_mtp_loss_fn(losses, mtp_losses, coeff: float):
    """``mean(losses) + coeff * mean(mtp_losses over the positions that
    have a target two ahead)`` (all but the last of each row; the model
    hands that one back as 0). Returns (total, main, mtp)."""
    main = jnp.mean(losses)
    rows, seq = mtp_losses.shape
    mtp = jnp.sum(mtp_losses) / (rows * (seq - 1))
    return main + coeff * mtp, main, mtp


def gpt_loss_fn(losses, loss_mask=None):
    """Mean loss over unmasked tokens (ref: loss_func in test_gpt_minimal.py)."""
    if loss_mask is None:
        return jnp.mean(losses)
    m = loss_mask.astype(jnp.float32)
    return jnp.sum(losses * m) / jnp.maximum(jnp.sum(m), 1.0)
