"""A published architecture file -> the model this program builds.

``examples/gpt/pretrain_gpt.py --arch-file <config.json>`` names a model by
its public ``config.json`` (Hugging Face keys). This module reads the
families the library can train and returns what
``apex_tpu.training.GPTTargetConfig`` carries: the five integers
every model has and the ``model`` description (``TransformerConfig``
fields).

**The share.** A model larger than the chips it trains on is divided: each
layer's routed experts over the ranks of an expert-parallel group, the
vocabulary's rows over a group, the layers over pipeline stages. One
program's share is what the arguments name — ``experts_held`` experts
from ``first_expert``, ``vocab_rows`` rows of the embedding and the head,
the first ``layers_kept`` layers — and every WIDTH stays as published: the
router still scores every expert and picks its top-k among all of them, the
program computes what its own experts add for the tokens routed to them,
and what the absent experts would add is left out (``transformer/moe.py``).
Nothing stands in for the other chips.
"""

from typing import Optional, Tuple

#: ``model_type`` values whose layer equations are DeepSeek-V3's: latent
#: attention, sigmoid-routed SwiGLU experts behind leading dense layers, a
#: shared expert, multi-token prediction
DEEPSEEK_V3_FAMILY = ("deepseek_v3", "joyai_llm_flash")


def described_model(
    arch: dict,
    *,
    layers_kept: Optional[int] = None,
    experts_held: Optional[int] = None,
    first_expert: int = 0,
    vocab_rows: Optional[int] = None,
    mtp_loss_coeff: float = 0.3,
    router_bias_update_speed: float = 0.0,
) -> Tuple[dict, dict]:
    """(sizes, model): ``sizes`` = ``layers, hidden, heads, vocab`` for
    ``GPTTargetConfig``; ``model`` its ``model`` description. Raises on a
    family, or a setting of a known family, that the library has no code
    for — a model is never approximated by its nearest neighbour."""
    kind = arch.get("model_type")
    if kind not in DEEPSEEK_V3_FAMILY:
        raise ValueError(
            f"model_type {kind!r}: no code for this family "
            f"(known: {DEEPSEEK_V3_FAMILY})")
    unsupported = {
        "n_group": 1, "topk_group": 1, "rope_scaling": None,
        "attention_bias": False, "moe_layer_freq": 1, "hidden_act": "silu",
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "tie_word_embeddings": False,
    }
    for key, only in unsupported.items():
        if arch.get(key, only) != only:
            raise NotImplementedError(
                f"{key}={arch[key]!r}: only {only!r} is implemented")
    layers = arch["num_hidden_layers"] if layers_kept is None else layers_kept
    dense = min(arch["first_k_dense_replace"], layers)
    sizes = dict(
        layers=layers, hidden=arch["hidden_size"],
        heads=arch["num_attention_heads"],
        vocab=arch["vocab_size"] if vocab_rows is None else vocab_rows)
    model = dict(
        normalization="rmsnorm",
        layernorm_epsilon=arch["rms_norm_eps"],
        add_bias_linear=False,
        activation="swiglu",
        ffn_hidden_size=arch["intermediate_size"],
        share_embeddings_and_output_weights=False,
        position_embedding_type="rope",
        rotary_base=float(arch["rope_theta"]),
        rotary_interleaved=bool(arch.get("rope_interleave", False)),
        attention_layer_kinds=("latent",) * layers,
        mlp_layer_kinds=("dense",) * dense + ("experts",) * (layers - dense),
        q_lora_rank=arch["q_lora_rank"],
        kv_lora_rank=arch["kv_lora_rank"],
        qk_nope_head_dim=arch["qk_nope_head_dim"],
        qk_rope_head_dim=arch["qk_rope_head_dim"],
        v_head_dim=arch["v_head_dim"],
        num_moe_experts=arch["n_routed_experts"],
        moe_top_k=arch["num_experts_per_tok"],
        moe_capacity_factor=None,
        moe_router="sigmoid",
        moe_norm_topk_prob=bool(arch["norm_topk_prob"]),
        moe_routed_scaling_factor=float(arch["routed_scaling_factor"]),
        moe_ffn_hidden_size=arch["moe_intermediate_size"],
        moe_gated_experts=True,
        moe_shared_experts=arch["n_shared_experts"],
        moe_experts_held=experts_held,
        moe_first_expert=first_expert,
        mtp_num_layers=arch.get("num_nextn_predict_layers", 0),
        mtp_loss_coeff=mtp_loss_coeff,
        moe_bias_update_speed=router_bias_update_speed,
    )
    return sizes, model
