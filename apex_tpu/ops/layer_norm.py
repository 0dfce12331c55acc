"""Fused LayerNorm / RMSNorm — flagship Pallas kernel #1.

Reference parity: ``fused_layer_norm_cuda`` (csrc/layer_norm_cuda.cpp:446-458,
layer_norm_cuda_kernel.cu — Welford rowwise stats) and the Python wrappers in
apex/normalization/fused_layer_norm.py (affine / non-affine / RMS / mixed-dtype
/ memory_efficient variants).

TPU design notes:
- math is always fp32 internally, inputs/outputs keep their dtype; parameters
  may have a different dtype than the input (this subsumes the reference's
  "Mixed" variants, fused_layer_norm.py:94-117 — no separate code path
  needed).
- the backward kernel recomputes row statistics from the saved input instead
  of saving mean/rstd: the block is already in VMEM and recompute is cheaper
  than the extra HBM traffic (the reference saves mean/invvar instead because
  CUDA blocks re-read from HBM).
- ``memory_efficient=True`` maps to ``jax.checkpoint`` (recompute-in-backward),
  the TPU idiom for the reference's recompute-from-output mode
  (fused_layer_norm.py ``memory_efficient`` arg).
- rows are padded to the Pallas block; hidden sizes that are not multiples of
  128 lanes fall back to the XLA path automatically.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.monitor.goodput.scopes import kernel_metadata
from apex_tpu.ops._dispatch import resolve_impl


def _pick_block_rows(rows: int, hidden: int) -> int:
    # Sized from a measured v5e failure, not theory: at 1<<20 elements/block
    # (4MB fp32) the bwd kernel's fp32 temporaries (x, dy, xhat, dyw, dx —
    # Mosaic stack-allocates each) blew the 16MB scoped-vmem limit by 32KB at
    # hidden=4096.  1<<18 (1MB fp32 per operand block) keeps the ~10-copy
    # working set near 10MB with double-buffering headroom; LN is HBM-bound,
    # so narrower blocks cost nothing measurable.
    budget = 1 << 18  # elements of fp32 per block operand
    br = max(8, min(512, budget // max(hidden, 1)))
    br = (br // 8) * 8
    return max(8, min(br, ((rows + 7) // 8) * 8))


# ---------------------------------------------------------------------------
# XLA reference implementations (autodiff provides the backward)
# ---------------------------------------------------------------------------


def _ln_ref(x, w, b, eps):
    # stats-in-f32 contract: mean/variance of bf16 activations lose all
    # significance in an 8-bit mantissa, so the reduction runs in f32 and
    # casts back (precision-auditor allowlist entry
    # "apex_tpu/ops/layer_norm.py", apex_tpu/analysis/allowlist.py)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def _rms_ref(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(x_ref, w_ref, dy_ref, dx_ref, dg_ref, db_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    dyw = dy * w
    m1 = jnp.mean(dyw, axis=1, keepdims=True)
    m2 = jnp.mean(dyw * xhat, axis=1, keepdims=True)
    dx_ref[:] = ((dyw - m1 - xhat * m2) * rstd).astype(dx_ref.dtype)
    # dgamma/dbeta accumulate across the (sequential) TPU grid into one
    # (1, hidden) block: a per-step (grid, hidden) partials array would need
    # a 1-sublane output block, which Mosaic rejects for grid > 1 (measured
    # on v5e: "last two dimensions ... divisible by 8 and 128")
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[:] = jnp.zeros_like(dg_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    dg_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


def _rms_fwd_kernel(x_ref, w_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=1, keepdims=True) + eps)
    y_ref[:] = (x * rstd * w_ref[:].astype(jnp.float32)).astype(y_ref.dtype)


def _rms_bwd_kernel(x_ref, w_ref, dy_ref, dx_ref, dg_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=1, keepdims=True) + eps)
    xhat = x * rstd
    dyw = dy * w
    m2 = jnp.mean(dyw * xhat, axis=1, keepdims=True)
    dx_ref[:] = ((dyw - xhat * m2) * rstd).astype(dx_ref.dtype)
    # accumulated across the sequential grid (see _ln_bwd_kernel)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[:] = jnp.zeros_like(dg_ref)

    dg_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)


def _pad_rows(x2d, block_rows):
    rows = x2d.shape[0]
    padded = ((rows + block_rows - 1) // block_rows) * block_rows
    if padded != rows:
        x2d = jnp.pad(x2d, ((0, padded - rows), (0, 0)))
    return x2d, padded


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln_pallas(x2d, w, b, eps, interpret):
    y, _ = _ln_pallas_fwd(x2d, w, b, eps, interpret)
    return y


def _ln_pallas_fwd(x2d, w, b, eps, interpret):
    rows, hidden = x2d.shape
    br = _pick_block_rows(rows, hidden)
    xp, padded = _pad_rows(x2d, br)
    grid = padded // br
    y = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((padded, hidden), x2d.dtype),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        interpret=interpret,
        name="ln_fwd",
        metadata=kernel_metadata("ln_fwd"),
    )(xp, w.reshape(1, -1), b.reshape(1, -1))
    return y[:rows], (x2d, w, b)


def _ln_pallas_bwd(eps, interpret, res, dy):
    x2d, w, b = res
    rows, hidden = x2d.shape
    br = _pick_block_rows(rows, hidden)
    xp, padded = _pad_rows(x2d, br)
    dyp, _ = _pad_rows(dy, br)
    grid = padded // br
    dx, dgp, dbp = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        out_shape=(
            jax.ShapeDtypeStruct((padded, hidden), x2d.dtype),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
        ),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ),
        interpret=interpret,
        name="ln_bwd",
        metadata=kernel_metadata("ln_bwd"),
    )(xp, w.reshape(1, -1), dyp)
    dg = dgp.reshape(-1).astype(w.dtype)
    db = dbp.reshape(-1).astype(b.dtype)
    return dx[:rows], dg, db


_ln_pallas.defvjp(_ln_pallas_fwd, _ln_pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_pallas(x2d, w, eps, interpret):
    y, _ = _rms_pallas_fwd(x2d, w, eps, interpret)
    return y


def _rms_pallas_fwd(x2d, w, eps, interpret):
    rows, hidden = x2d.shape
    br = _pick_block_rows(rows, hidden)
    xp, padded = _pad_rows(x2d, br)
    grid = padded // br
    y = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((padded, hidden), x2d.dtype),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        interpret=interpret,
        name="rms_fwd",
        metadata=kernel_metadata("rms_fwd"),
    )(xp, w.reshape(1, -1))
    return y[:rows], (x2d, w)


def _rms_pallas_bwd(eps, interpret, res, dy):
    x2d, w = res
    rows, hidden = x2d.shape
    br = _pick_block_rows(rows, hidden)
    xp, padded = _pad_rows(x2d, br)
    dyp, _ = _pad_rows(dy, br)
    grid = padded // br
    dx, dgp = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps=eps),
        out_shape=(
            jax.ShapeDtypeStruct((padded, hidden), x2d.dtype),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
        ),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ),
        interpret=interpret,
        name="rms_bwd",
        metadata=kernel_metadata("rms_bwd"),
    )(xp, w.reshape(1, -1), dyp)
    dg = dgp.reshape(-1).astype(w.dtype)
    return dx[:rows], dg


_rms_pallas.defvjp(_rms_pallas_fwd, _rms_pallas_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def layer_norm(
    x,
    weight=None,
    bias=None,
    eps: float = 1e-5,
    memory_efficient: bool = False,
    impl: str = "auto",
):
    """Fused layer normalization over the last dimension.

    Ref: apex.normalization.FusedLayerNorm (normalization/fused_layer_norm.py:230)
    and fused_layer_norm_cuda.forward_affine (layer_norm_cuda.cpp:446).
    """
    hidden = x.shape[-1]
    use_pallas, interpret = resolve_impl(impl)
    affine = weight is not None
    if use_pallas and hidden % 128 == 0 and affine:
        w = weight
        b = bias if bias is not None else jnp.zeros((hidden,), w.dtype)
        fn = lambda xx, ww, bb: _ln_pallas(
            xx.reshape(-1, hidden), ww, bb, eps, interpret
        ).reshape(xx.shape)
    else:
        fn = lambda xx, ww, bb: _ln_ref(xx, ww, bb, eps)
        w, b = weight, bias
    if memory_efficient:
        fn = jax.checkpoint(fn)
    return fn(x, w, b)


def rms_norm(
    x,
    weight=None,
    eps: float = 1e-5,
    memory_efficient: bool = False,
    impl: str = "auto",
):
    """Fused RMS normalization (ref: FusedRMSNorm, fused_layer_norm.py:329)."""
    hidden = x.shape[-1]
    use_pallas, interpret = resolve_impl(impl)
    if use_pallas and hidden % 128 == 0 and weight is not None:
        fn = lambda xx, ww: _rms_pallas(
            xx.reshape(-1, hidden), ww, eps, interpret
        ).reshape(xx.shape)
        w = weight
    else:
        fn = lambda xx, ww: _rms_ref(xx, ww, eps)
        w = weight
    if memory_efficient:
        fn = jax.checkpoint(fn)
    return fn(x, w)
