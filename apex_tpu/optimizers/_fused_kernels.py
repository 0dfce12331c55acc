"""Flat-buffer Pallas optimizer kernels.

Reference parity: amp_C.multi_tensor_adam (csrc/multi_tensor_adam.cu:13-14)
driven by the chunked multi_tensor_apply engine
(csrc/multi_tensor_apply.cuh:19-133) — one kernel launch updates every
parameter tensor. TPU design: the pytree is flattened ONCE into a padded
fp32 buffer (ops/multi_tensor.flatten_pytree) and a single Pallas kernel
walks it in CHUNK_SIZE blocks; the (8,128)-aligned padding removes all the
reference's per-chunk remainder handling.

The jnp twin (`_adam_flat_ref`) is bit-identical math used for the
impl="xla" path and CPU tests; `fused_adam(fuse="flat")` in fused_adam.py
plugs either into the optax interface. Tree Adam is the default (no
flatten round-trip); the flat l2norm serves buffers that are flat already,
which is why the ZeRO optimizers use it. Neither comparison is measured on
the chip.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.monitor.goodput.scopes import kernel_metadata
from apex_tpu.ops._dispatch import resolve_impl
from apex_tpu.ops.multi_tensor import CHUNK_SIZE

_LANES = 128
_ROWS_PER_CHUNK = CHUNK_SIZE // _LANES  # 512 rows of 128 f32 lanes


def _adam_flat_kernel(
    sc_ref, g_ref, p_ref, m_ref, v_ref,
    upd_ref, m_out_ref, v_out_ref,
    *, lr, beta1, beta2, eps, weight_decay, adam_w_mode,
):
    """One CHUNK of the Adam update (ref multi_tensor_adam.cu:13-14 math:
    ADAM_MODE_0 = AdamW decoupled decay, ADAM_MODE_1 = L2 into the grad)."""
    bc1 = sc_ref[0, 0]  # 1 - beta1^t (bias correction, traced via step)
    bc2 = sc_ref[0, 1]
    g = g_ref[...]
    p = p_ref[...]
    if not adam_w_mode and weight_decay != 0.0:
        g = g + weight_decay * p
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * g * g
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w_mode and weight_decay != 0.0:
        upd = upd + weight_decay * p
    upd_ref[...] = -lr * upd
    m_out_ref[...] = m
    v_out_ref[...] = v


def _adam_flat_ref(g, p, m, v, bc1, bc2, *, lr, beta1, beta2, eps,
                   weight_decay, adam_w_mode):
    """jnp twin of the kernel — identical math, XLA-fused."""
    if not adam_w_mode and weight_decay != 0.0:
        g = g + weight_decay * p
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w_mode and weight_decay != 0.0:
        upd = upd + weight_decay * p
    return -lr * upd, m, v


def adam_flat(
    g_flat, p_flat, m_flat, v_flat, bc1, bc2,
    *, lr, beta1, beta2, eps, weight_decay, adam_w_mode,
    impl: str = "auto",
):
    """Adam over padded flat fp32 buffers; returns (update, m, v).

    All four buffers must share the same length, a multiple of CHUNK_SIZE
    (flatten_pytree guarantees this). ``bc1``/``bc2`` are the (traced)
    bias-correction denominators; everything else is static.
    """
    (n,) = g_flat.shape
    assert n % CHUNK_SIZE == 0, f"flat buffer ({n}) not CHUNK_SIZE-padded"
    use_pallas, interpret = resolve_impl(impl)
    if not use_pallas:
        return _adam_flat_ref(
            g_flat, p_flat, m_flat, v_flat, bc1, bc2,
            lr=lr, beta1=beta1, beta2=beta2, eps=eps,
            weight_decay=weight_decay, adam_w_mode=adam_w_mode,
        )
    rows = n // _LANES
    view = lambda a: a.reshape(rows, _LANES)
    sc = jnp.stack([
        jnp.asarray(bc1, jnp.float32), jnp.asarray(bc2, jnp.float32)
    ]).reshape(1, 2)
    grid = (n // CHUNK_SIZE,)
    chunk_spec = pl.BlockSpec(
        (_ROWS_PER_CHUNK, _LANES), lambda i: (i, 0),
        memory_space=pltpu.VMEM,
    )
    kernel = functools.partial(
        _adam_flat_kernel, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
        weight_decay=weight_decay, adam_w_mode=adam_w_mode,
    )
    upd, m, v = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM),
            chunk_spec, chunk_spec, chunk_spec, chunk_spec,
        ],
        out_specs=(chunk_spec, chunk_spec, chunk_spec),
        interpret=interpret,
        name="adam_flat",
        metadata=kernel_metadata("adam_flat"),
    )(sc, view(g_flat), view(p_flat), view(m_flat), view(v_flat))
    return upd.reshape(n), m.reshape(n), v.reshape(n)


def _l2norm_flat_kernel(x_ref, acc_ref):
    """Partial sum-of-squares per chunk, accumulated across the grid into
    one (1,1) SMEM cell (ref multi_tensor_l2norm_kernel.cu's two-stage
    block reduction collapsed into a sequential-grid accumulation)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[0, 0] = 0.0

    x = x_ref[...]
    acc_ref[0, 0] += jnp.sum(x * x)


def sumsq_flat(x_flat, impl: str = "auto"):
    """Sum of squares of a flat buffer.

    Accepts any length: internally zero-padded to a CHUNK_SIZE multiple for
    the Pallas grid (zeros contribute nothing to the sum). This is the
    reduction ZeRO shards feed — a per-rank shard of a CHUNK-padded buffer
    (`padded_total / dp`) is generally NOT itself CHUNK-aligned.
    """
    (n,) = x_flat.shape
    use_pallas, interpret = resolve_impl(impl)
    xf = x_flat.astype(jnp.float32)
    if not use_pallas:
        return jnp.sum(xf * xf)
    if n % CHUNK_SIZE:
        xf = jnp.pad(xf, (0, CHUNK_SIZE - n % CHUNK_SIZE))
        (n,) = xf.shape
    rows = n // _LANES
    sq = pl.pallas_call(
        _l2norm_flat_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        grid=(n // CHUNK_SIZE,),
        in_specs=[
            pl.BlockSpec(
                (_ROWS_PER_CHUNK, _LANES), lambda i: (i, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM
        ),
        interpret=interpret,
        name="sumsq_flat",
        metadata=kernel_metadata("sumsq_flat"),
    )(xf.reshape(rows, _LANES))
    return sq[0, 0]


def l2norm_flat(x_flat, impl: str = "auto"):
    """Global L2 norm of a flat buffer (padding zeros contribute 0).

    The flat path is the default wherever the data already lives in one
    buffer (ZeRO shards in distributed_fused_lamb; fused_adam's flat
    engine); against the tree-based ``multi_tensor_l2norm`` it is not
    measured on the chip.
    """
    return jnp.sqrt(sumsq_flat(x_flat, impl=impl))
