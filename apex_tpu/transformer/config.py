"""Transformer configuration.

The reference configures its Megatron-style transformer through the 188-flag
argparse namespace (testing/arguments.py:23) plus constructor kwargs threaded
through standalone_transformer_lm.py. Here the whole surface collapses into
one frozen dataclass that is hashable (so flax modules can hold it as a
static attribute) and carries the TPU-specific knobs (compute dtype, mesh
axis names, attention impl) alongside the reference's architectural ones.
"""

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Architecture + parallelism knobs for the Megatron-style stack.

    Field provenance (reference): hidden_size/num_layers/num_attention_heads/
    ffn_hidden_size/kv_channels mirror testing/arguments.py `_add_network_size_args`;
    hidden_dropout/attention_dropout ditto; layernorm_epsilon,
    apply_residual_connection_post_layernorm and fp32_residual_connection come
    from the transformer-layer flags used by standalone_transformer_lm.py.
    """

    num_layers: int
    hidden_size: int
    num_attention_heads: int
    vocab_size: int = 0
    max_position_embeddings: int = 0
    ffn_hidden_size: Optional[int] = None  # defaults to 4*hidden_size
    kv_channels: Optional[int] = None  # defaults to hidden_size // heads
    # GQA (extension; absent in the reference): number of KV heads. None =
    # MHA. Must divide num_attention_heads; with tp>1 must also divide by
    # tp (KV heads are tensor-sharded like Q heads).
    num_query_groups: Optional[int] = None
    # sliding-window attention (extension; mistral-style). None = full
    # causal. Applied only to causal self-attention.
    attention_window: Optional[int] = None

    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layernorm_epsilon: float = 1e-5
    normalization: str = "layernorm"  # "layernorm" | "rmsnorm"
    # Megatron --disable-bias-linear: bias-free attention/MLP projections
    # (llama-family models). LayerNorm/RMSNorm params are unaffected.
    add_bias_linear: bool = True
    activation: str = "gelu"  # "gelu" | "geglu" | "relu" | "swiglu"
    apply_residual_connection_post_layernorm: bool = False
    fp32_residual_connection: bool = False
    apply_query_key_layer_scaling: bool = False
    # NOTE: softmax math is ALWAYS fp32 internally (ops/softmax.py,
    # ops/attention.py) — the reference's attention_softmax_in_fp32 flag has
    # no "off" position on TPU. The attention mask type is a property of the
    # model (GPT=causal, BERT=padding) and is passed to the modules directly.

    position_embedding_type: str = "learned"  # "learned" | "rope" | "none"
    rotary_percent: float = 1.0
    rotary_base: float = 10000.0  # RoPE theta (llama-3 uses 500000)
    # rotate consecutive channel pairs (0,1), (2,3), ... instead of channel
    # i with i + rot_dim/2 (the DeepSeek-family layout)
    rotary_interleaved: bool = False

    # what each layer is (``layer_kinds(i)``). None = every layer alike:
    # attention "mha", MLP "experts" when num_moe_experts is set, else
    # "dense". A tuple of one entry a layer says otherwise: attention
    # "mha" | "latent"; MLP "dense" | "experts" (leading dense layers
    # before expert layers are ("dense", "experts", "experts", ...)).
    attention_layer_kinds: Optional[Tuple[str, ...]] = None
    mlp_layer_kinds: Optional[Tuple[str, ...]] = None
    # latent attention (DeepSeek-V2/V3 MLA, training form): low-rank q and
    # kv projections, a rope part all heads share, a no-rope part a head.
    # q/k are qk_nope_head_dim + qk_rope_head_dim wide, v v_head_dim
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None

    # parallelism
    sequence_parallel: bool = False
    tensor_axis: str = "tp"
    # context parallelism (no reference counterpart — SURVEY.md §2.5):
    # shard the sequence over the 'cp' mesh axis inside attention.
    # None | "ring" (ppermute K/V ring) | "ulysses" (all-to-all head swap)
    context_parallel_mode: Optional[str] = None
    context_axis: str = "cp"
    # mixture-of-experts (no reference counterpart — EP extension):
    # num_moe_experts switches the MLP block to MoEMLP; experts shard over
    # moe_expert_axis (None = local experts)
    num_moe_experts: Optional[int] = None
    moe_top_k: int = 1
    # slots an expert has for each top-k pass, as a multiple of tokens /
    # experts; assignments beyond them are dropped (Switch/GShard). None =
    # dropless: every assignment is computed
    moe_capacity_factor: Optional[float] = 1.25
    moe_expert_axis: Optional[str] = None
    moe_aux_loss_coeff: float = 0.01
    # "softmax" (Switch/GShard: gates are the chosen probabilities) |
    # "sigmoid" (DeepSeek-V3 noaux_tc: experts chosen by score + a bias
    # that takes no gradient, gates from the scores alone)
    moe_router: str = "softmax"
    moe_norm_topk_prob: bool = False  # gates divided by their sum
    moe_routed_scaling_factor: float = 1.0
    moe_ffn_hidden_size: Optional[int] = None  # None = ffn_hidden_size
    moe_gated_experts: bool = False  # SwiGLU experts (gate, up, down)
    moe_shared_experts: int = 0  # always-on experts beside the routed
    # the share of the routed experts this program holds, outside an
    # expert axis: [moe_first_expert, moe_first_expert + moe_experts_held)
    # of num_moe_experts. The router stays num_moe_experts wide; what the
    # absent experts would add is left out (None = all of them)
    moe_experts_held: Optional[int] = None
    moe_first_expert: int = 0
    moe_impl: str = "auto"  # the experts' grouped matmul (ops._dispatch)
    # balancing without an auxiliary loss (the sigmoid router's bias): what
    # the step builder moves each expert's bias by after a step, up where the
    # expert took fewer assignments than the mean, down where more; 0 holds
    # the bias where it is (DeepSeek-V3 trained with 0.001)
    moe_bias_update_speed: float = 0.0
    # multi-token prediction (DeepSeek-V3): modules after the trunk, each
    # one layer of the stack's last kind predicting one token further
    mtp_num_layers: int = 0
    mtp_loss_coeff: float = 0.3
    recompute_granularity: Optional[str] = None  # None | "full" | "selective"

    # telemetry (apex_tpu.monitor): sow a per-layer output-RMS tap
    # ("layer_out_rms" under the "intermediates" collection) from every
    # ParallelTransformerLayer. Off by default — readers must pass
    # mutable=["intermediates"] to apply() to collect it.
    collect_layer_metrics: bool = False

    # dtypes: params live in fp32, compute in bf16 by default (TPU-native
    # replacement for the reference's fp16 O2 regime)
    params_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    # attention backend: "auto" → Pallas flash attention on TPU
    attention_impl: str = "auto"

    share_embeddings_and_output_weights: bool = True

    def __post_init__(self):
        if self.context_parallel_mode not in (None, "ring", "ulysses"):
            raise ValueError(
                f"context_parallel_mode must be None, 'ring', or 'ulysses'; "
                f"got {self.context_parallel_mode!r}"
            )
        if self.ffn_hidden_size is None:
            object.__setattr__(self, "ffn_hidden_size", 4 * self.hidden_size)
        if self.kv_channels is None:
            assert self.hidden_size % self.num_attention_heads == 0
            object.__setattr__(
                self, "kv_channels", self.hidden_size // self.num_attention_heads
            )
        for field, known in (("attention_layer_kinds", ("mha", "latent")),
                             ("mlp_layer_kinds", ("dense", "experts"))):
            kinds = getattr(self, field)
            if kinds is None:
                continue
            object.__setattr__(self, field, tuple(kinds))
            if len(kinds) != self.num_layers or set(kinds) - set(known):
                raise ValueError(
                    f"layer kinds {kinds!r}: one of {known} for each of "
                    f"{self.num_layers} layers")
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_router {self.moe_router!r}")

    def layer_kinds(self, i: int) -> Tuple[str, str]:
        """(attention kind, MLP kind) of layer ``i``; an index past the
        stack (a multi-token-prediction block) is of the last layer's."""
        i = min(i, self.num_layers - 1)
        attn = (self.attention_layer_kinds[i]
                if self.attention_layer_kinds is not None else "mha")
        if self.mlp_layer_kinds is not None:
            return attn, self.mlp_layer_kinds[i]
        return attn, "experts" if self.num_moe_experts is not None else "dense"
