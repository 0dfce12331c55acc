"""Analytic model-FLOPs counters and MFU / throughput arithmetic.

Model-FLOPs-utilization is ``(model FLOPs per second) / (hardware peak
FLOPs per second)`` where the numerator counts only the FLOPs the MODEL
mathematically requires (the PaLM/Chinchilla convention TorchTitan also
reports): matmul FLOPs at 2*m*n*k, backward at 2x forward, and NOTHING
for recomputation — activation checkpointing re-spends hardware FLOPs
without doing more model math, so MFU honestly drops when remat is on.

The per-second numerator must come from a clock that waits for the device
(a barrier-synced interval timer): a wall clock around an un-awaited
dispatch measures the enqueue, and an MFU computed from it is fiction.
``chip_smoke.py``'s clock phase checks that ``block_until_ready`` waits on
the machine at hand.

Counters are exact closed forms over TransformerConfig so tests can check
them against hand-counted tiny configs digit for digit.
"""

import os
from typing import Optional

__all__ = [
    "transformer_layer_flops_per_token",
    "gpt_flops_per_token",
    "bert_flops_per_token",
    "training_flops_per_step",
    "tokens_per_second",
    "mfu",
    "peak_flops_per_device",
]

#: Dense-matmul peak (bf16) per chip, by device-kind substring. Sources:
#: published TPU specs (v5e 197 TFLOP/s; v4 275; v3 123; v5p 459;
#: v6e 918). CPU/unknown kinds return None — an MFU against a made-up
#: peak is worse than none.
_PEAK_FLOPS = (
    ("v6 lite", 918e12),  # libtpu reports v6e as "TPU v6 lite"
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # ... and v5e as "TPU v5 lite"
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
)


def peak_flops_per_device(device=None) -> Optional[float]:
    """Peak dense FLOP/s of one device, or None when unknown.

    ``APEX_TPU_PEAK_FLOPS`` overrides (benchmarks pinning a number, tests,
    and accelerators missing from the table).
    """
    env = os.environ.get("APEX_TPU_PEAK_FLOPS")
    if env:
        return float(env)
    if device is None:
        import jax

        devices = jax.devices()
        if not devices:
            return None
        device = devices[0]
    kind = getattr(device, "device_kind", "").lower()
    for sub, peak in _PEAK_FLOPS:
        if sub in kind:
            return peak
    return None


def _cfg_dims(cfg):
    h = cfg.hidden_size
    heads = cfg.num_attention_heads
    kv_heads = cfg.num_query_groups or heads
    head_dim = cfg.kv_channels or h // heads
    ffn = cfg.ffn_hidden_size or 4 * h
    return h, heads, kv_heads, head_dim, ffn


def _attention_flops_per_token(cfg, seq_len: int, kind: str) -> float:
    """Projections + scores + context of one attention block, forward."""
    h, heads, kv_heads, head_dim, _ = _cfg_dims(cfg)
    if kind == "latent":
        # low-rank q (down, up), joint kv down-projection with the shared
        # rope key, kv up-projection, output; scores over nope + rope
        # channels, context over v_head_dim
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        proj = (h * cfg.q_lora_rank + cfg.q_lora_rank * heads * qk
                + h * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                + cfg.kv_lora_rank * heads * (cfg.qk_nope_head_dim
                                              + cfg.v_head_dim)
                + heads * cfg.v_head_dim * h)
        return float(2 * proj
                     + 2 * seq_len * heads * (qk + cfg.v_head_dim))
    q = heads * head_dim
    kv = kv_heads * head_dim
    return float(2 * h * (q + 2 * kv) + 4 * seq_len * q + 2 * q * h)


def transformer_layer_flops_per_token(cfg, seq_len: int,
                                      layer: int = 0) -> float:
    """Forward matmul FLOPs per token for ONE ParallelTransformerLayer,
    of the kinds ``cfg.layer_kinds(layer)`` gives it.

    Counts (2*m*n*k per matmul, per token):

    - QKV projection: ``2*h*(q + 2*kv)`` where q = heads*head_dim and
      kv = kv_heads*head_dim (GQA shrinks the K/V columns); latent
      attention: its five projections instead;
    - attention scores + context: ``2*s*q`` each — every query token
      multiplies against s keys and weights s values (causal masking
      halves the REACHABLE area, but the dense kernels here compute the
      full s x s product, and MFU counts the math the model runs);
    - output projection: ``2*q*h``;
    - dense MLP: ``2*h*ffn + 2*ffn*h``, plus ``2*h*ffn`` more for the
      extra gate matmul of geglu/swiglu;
    - expert MLP: router ``2*h*E``; each of the ``moe_top_k`` experts a
      token runs costs two matmuls of ``h x moe_ffn`` (three when
      ``moe_gated_experts``), counted for the share of them that lands on
      the experts held here (``moe_experts_held / E``; all of them when no
      share is set); ``moe_shared_experts`` more run for every token.
      Each token mathematically runs top_k experts, so a top-2 MoE spends
      ~2x the dense MLP FLOPs. Capacity-dropped tokens still count (the
      convention counts the model's assignment math; drops are a lossy
      implementation detail, and counting them would make MFU improve when
      the router overflows).

    Element-wise work (norms, softmax, residuals, gating combines) is
    O(h) per token and omitted, per the standard model-FLOPs convention.
    """
    h, _, _, _, ffn = _cfg_dims(cfg)
    kinds = getattr(cfg, "layer_kinds", None)
    attn_kind, mlp_kind = kinds(layer) if kinds else (
        "mha", "experts" if getattr(cfg, "num_moe_experts", None)
        else "dense")
    attn = _attention_flops_per_token(cfg, seq_len, attn_kind)
    if mlp_kind == "experts":
        num_experts = cfg.num_moe_experts
        top_k = getattr(cfg, "moe_top_k", 1) or 1
        e_ffn = getattr(cfg, "moe_ffn_hidden_size", None) or ffn
        n_mats = 3 if getattr(cfg, "moe_gated_experts", False) else 2
        held = getattr(cfg, "moe_experts_held", None)
        share = 1.0 if held is None else held / num_experts
        expert = n_mats * 2 * h * e_ffn
        mlp = (2 * h * num_experts + top_k * share * expert
               + getattr(cfg, "moe_shared_experts", 0) * expert)
    else:
        n_mats = 3 if cfg.activation in ("geglu", "swiglu") else 2
        mlp = n_mats * 2 * h * ffn
    return float(attn + mlp)


def gpt_flops_per_token(cfg, seq_len: Optional[int] = None) -> float:
    """Forward FLOPs per token of the GPT testing model: the layer stack
    plus the logit matmul ``2*h*vocab``. A multi-token-prediction block
    adds one layer of the last kind, its ``2h x h`` joining projection and
    the head a second time. Embedding lookups are gathers (0 matmul
    FLOPs)."""
    s = seq_len if seq_len is not None else cfg.max_position_embeddings
    layers = sum(transformer_layer_flops_per_token(cfg, s, i)
                 for i in range(cfg.num_layers))
    head = 2 * cfg.hidden_size * cfg.vocab_size
    mtp = getattr(cfg, "mtp_num_layers", 0) * (
        transformer_layer_flops_per_token(cfg, s, cfg.num_layers)
        + 2 * 2 * cfg.hidden_size * cfg.hidden_size + head)
    return float(layers + head + mtp)


def bert_flops_per_token(cfg, seq_len: Optional[int] = None) -> float:
    """Forward FLOPs per token of the BERT testing model: layer stack +
    LM head (dense h->h + vocab projection) — the binary head is O(h)
    per SEQUENCE and ignored."""
    s = seq_len if seq_len is not None else cfg.max_position_embeddings
    h = cfg.hidden_size
    layers = cfg.num_layers * transformer_layer_flops_per_token(cfg, s)
    lm_head = 2 * h * h + 2 * h * cfg.vocab_size
    return float(layers + lm_head)


def training_flops_per_step(
    flops_per_token_fwd: float, tokens_per_step: int
) -> float:
    """Model FLOPs of one optimizer step: forward + backward = 3x forward
    (backward costs ~2x: one matmul each for input and weight grads)."""
    return 3.0 * flops_per_token_fwd * tokens_per_step


def tokens_per_second(tokens_per_step: int, seconds_per_step: float) -> float:
    if seconds_per_step <= 0:
        raise ValueError(f"seconds_per_step must be > 0, got {seconds_per_step}")
    return tokens_per_step / seconds_per_step


def mfu(
    flops_per_step: float,
    seconds_per_step: float,
    num_devices: int,
    peak_flops: Optional[float] = None,
) -> Optional[float]:
    """Model-FLOPs utilization in [0, 1]-ish, or None when the peak is
    unknown (see :func:`peak_flops_per_device`). > 1 means the timing or
    the peak table is wrong — callers should surface it, not clamp it."""
    if peak_flops is None:
        peak_flops = peak_flops_per_device()
    if peak_flops is None or seconds_per_step <= 0:
        return None
    return flops_per_step / (seconds_per_step * num_devices * peak_flops)
