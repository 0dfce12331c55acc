"""Fused gradient clipping.

Reference parity: apex.contrib.clip_grad.clip_grad_norm_
(contrib/clip_grad/clip_grad.py:16) — global-norm clip using
multi_tensor_l2norm + multi_tensor_scale.

Engine choice (not measured on the chip): the tree-based norm stays because
the input here is a pytree — a flat reduction would first copy every leaf
into one buffer. ZeRO optimizers, whose shards ARE flat, use
``_fused_kernels.sumsq_flat`` instead.
"""

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.multi_tensor import multi_tensor_l2norm


def clip_grad_norm(
    grads: Any, max_norm: float, norm_type: float = 2.0
) -> Tuple[Any, jax.Array]:
    """Clip grads to global ``max_norm``; returns (clipped_grads, total_norm).

    Functional: returns new grads instead of mutating in place.
    """
    if norm_type == 2.0:
        total_norm = multi_tensor_l2norm(grads)
    elif norm_type == float("inf"):
        leaves = jax.tree_util.tree_leaves(grads)
        total_norm = jnp.max(
            jnp.stack([jnp.max(jnp.abs(x.astype(jnp.float32))) for x in leaves])
        )
    else:
        leaves = jax.tree_util.tree_leaves(grads)
        acc = sum(
            jnp.sum(jnp.abs(x.astype(jnp.float32)) ** norm_type) for x in leaves
        )
        total_norm = acc ** (1.0 / norm_type)
    coef = jnp.minimum(max_norm / (total_norm + 1e-6), 1.0)
    clipped = jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * coef).astype(g.dtype), grads
    )
    return clipped, total_norm
