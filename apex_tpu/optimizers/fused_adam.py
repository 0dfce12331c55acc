"""Fused Adam / AdamW.

Reference parity: apex.optimizers.FusedAdam (optimizers/fused_adam.py:4,
step :127) backed by amp_C.multi_tensor_adam (csrc/multi_tensor_adam.cu) —
``adam_w_mode`` selects decoupled weight decay, ``bias_correction`` the
1/(1-beta^t) terms. The CUDA "capturable" mode (GPU-resident lr/step for
CUDA graphs) is inherent here: everything, including the step count, lives
on device inside jit.
"""

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax


class FusedAdamState(NamedTuple):
    step: jax.Array
    exp_avg: Any  # first moment, fp32
    exp_avg_sq: Any  # second moment, fp32


def fused_adam(
    lr: float = 1e-3,
    bias_correction: bool = True,
    betas: Tuple[float, float] = (0.9, 0.999),
    eps: float = 1e-8,
    adam_w_mode: bool = True,
    weight_decay: float = 0.0,
    fuse: str = "tree",
) -> optax.GradientTransformation:
    """Optax transform matching amp_C.multi_tensor_adam semantics.

    ``fuse`` selects the update engine:
    - ``"tree"``: per-leaf tree_map math, fused by XLA inside the caller's
      jit. The default, and what every benchmark cell runs: it has no
      flatten/unflatten round-trip. The comparison with ``"flat"`` on the
      chip is not measured;
    - ``"flat"``: the reference's multi_tensor design — moments live in one
      CHUNK_SIZE-padded fp32 buffer and a single Pallas kernel
      (``_fused_kernels.adam_flat``) updates everything per step.
    """
    beta1, beta2 = betas
    if fuse not in ("tree", "flat"):
        raise ValueError(f"unknown fuse mode {fuse!r}; expected tree|flat")

    def _bias_corrections(stepf):
        if bias_correction:
            return 1.0 - beta1**stepf, 1.0 - beta2**stepf
        one = jnp.asarray(1.0, jnp.float32)
        return one, one

    def init_fn(params):
        zeros = lambda t: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), t
        )
        return FusedAdamState(
            step=jnp.zeros((), jnp.int32), exp_avg=zeros(params), exp_avg_sq=zeros(params)
        )

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("fused_adam requires params")
        step = state.step + 1
        bc1, bc2 = _bias_corrections(step.astype(jnp.float32))

        def _g(g, p):
            # master-accumulation contract: bf16/f16 grads enter the Adam
            # math in f32 exactly once, here (precision-auditor allowlist
            # entry "apex_tpu/optimizers/", apex_tpu/analysis/allowlist.py)
            gf = g.astype(jnp.float32)
            if not adam_w_mode and weight_decay != 0.0:
                gf = gf + weight_decay * p.astype(jnp.float32)  # L2 mode (ADAM_MODE_1)
            return gf

        geff = jax.tree_util.tree_map(_g, grads, params)
        m = jax.tree_util.tree_map(
            lambda g, m: beta1 * m + (1.0 - beta1) * g, geff, state.exp_avg
        )
        v = jax.tree_util.tree_map(
            lambda g, v: beta2 * v + (1.0 - beta2) * g * g, geff, state.exp_avg_sq
        )

        def _upd(p, m, v):
            upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if adam_w_mode and weight_decay != 0.0:
                upd = upd + weight_decay * p.astype(jnp.float32)  # decoupled (ADAM_MODE_0)
            return (-lr * upd).astype(p.dtype)

        updates = jax.tree_util.tree_map(_upd, params, m, v)
        return updates, FusedAdamState(step=step, exp_avg=m, exp_avg_sq=v)

    def flat_init_fn(params):
        from apex_tpu.ops.multi_tensor import CHUNK_SIZE

        # padded length from shapes alone — no transient fp32 flat copy
        total = sum(
            int(x.size) for x in jax.tree_util.tree_leaves(params)
        )
        padded = max(CHUNK_SIZE, -(-total // CHUNK_SIZE) * CHUNK_SIZE)
        zeros = jnp.zeros((padded,), jnp.float32)
        return FusedAdamState(
            step=jnp.zeros((), jnp.int32), exp_avg=zeros, exp_avg_sq=zeros
        )

    def flat_update_fn(grads, state, params=None):
        from apex_tpu.optimizers._fused_kernels import adam_flat
        from apex_tpu.ops.multi_tensor import flatten_pytree, unflatten_pytree

        if params is None:
            raise ValueError("fused_adam requires params")
        step = state.step + 1
        bc1, bc2 = _bias_corrections(step.astype(jnp.float32))
        g_flat, _ = flatten_pytree(grads, dtype=jnp.float32)
        p_flat, spec = flatten_pytree(params, dtype=jnp.float32)
        upd_flat, m_flat, v_flat = adam_flat(
            g_flat, p_flat, state.exp_avg, state.exp_avg_sq, bc1, bc2,
            lr=lr, beta1=beta1, beta2=beta2, eps=eps,
            weight_decay=weight_decay, adam_w_mode=adam_w_mode,
        )
        # spec carries params' dtypes, so updates cast back per leaf
        updates = unflatten_pytree(upd_flat, spec)
        return updates, FusedAdamState(
            step=step, exp_avg=m_flat, exp_avg_sq=v_flat
        )

    if fuse == "flat":
        return optax.GradientTransformation(flat_init_fn, flat_update_fn)
    return optax.GradientTransformation(init_fn, update_fn)


class FusedAdam:
    """Class-style wrapper mirroring the reference constructor signature."""

    def __new__(
        cls,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        adam_w_mode: bool = True,
        weight_decay: float = 0.0,
        amsgrad: bool = False,
        capturable: bool = False,
        master_weights: bool = False,
        **_unused,
    ):
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        del capturable, master_weights  # inherent under jit / see amp.AmpOptimizer
        return fused_adam(
            lr=lr,
            bias_correction=bias_correction,
            betas=betas,
            eps=eps,
            adam_w_mode=adam_w_mode,
            weight_decay=weight_decay,
        )
