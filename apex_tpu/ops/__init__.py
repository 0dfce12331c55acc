"""Fused ops for TPU (Pallas kernels + XLA-fused compositions).

Reference parity: the native kernel layer csrc/ + apex/normalization +
apex/mlp + apex/fused_dense + apex/transformer/functional (see SURVEY.md
section 2.4). Each op ships a pure-jnp reference implementation and, where a
custom kernel pays off on TPU, a Pallas kernel with a custom_vjp; dispatch is
automatic (Pallas on TPU, interpreted Pallas or jnp elsewhere).
"""

from apex_tpu.ops.multi_tensor import (
    CHUNK_SIZE,
    flatten,
    unflatten,
    flatten_pytree,
    unflatten_pytree,
    multi_tensor_applier,
    multi_tensor_scale,
    multi_tensor_axpby,
    multi_tensor_l2norm,
)
from apex_tpu.ops.layer_norm import layer_norm, rms_norm
from apex_tpu.ops.softmax import (
    scaled_softmax,
    scaled_masked_softmax,
    scaled_upper_triang_masked_softmax,
    generic_scaled_masked_softmax,
    fused_scale_mask_softmax,
)
from apex_tpu.ops.rope import (
    apply_rotary_pos_emb,
    apply_rotary_pos_emb_cached,
    rope_frequencies,
)
from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
from apex_tpu.ops.fused_dense import fused_dense, fused_dense_gelu_dense
from apex_tpu.ops.mlp import mlp_apply, mlp_init
from apex_tpu.ops.attention import (
    flash_attention,
    latent_flash_attention,
    paged_decode_attention,
)

__all__ = [
    "CHUNK_SIZE",
    "flatten",
    "unflatten",
    "flatten_pytree",
    "unflatten_pytree",
    "multi_tensor_applier",
    "multi_tensor_scale",
    "multi_tensor_axpby",
    "multi_tensor_l2norm",
    "layer_norm",
    "rms_norm",
    "scaled_softmax",
    "scaled_masked_softmax",
    "scaled_upper_triang_masked_softmax",
    "generic_scaled_masked_softmax",
    "fused_scale_mask_softmax",
    "apply_rotary_pos_emb",
    "rope_frequencies",
    "apply_rotary_pos_emb_cached",
    "softmax_cross_entropy_loss",
    "fused_dense",
    "fused_dense_gelu_dense",
    "mlp_apply",
    "mlp_init",
    "flash_attention",
    "latent_flash_attention",
    "paged_decode_attention",
]
