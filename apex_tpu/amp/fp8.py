"""Minimal FP8 delayed-scaling recipe.

Reference parity: the reference exposes the amax-reduction PROCESS GROUPS
for FP8 training (apex/transformer/parallel_state.py:280-292) but no
recipe; the recipe itself is transformer-engine's delayed scaling.  This
module supplies the minimal, testable core of that recipe on TPU:

- per-tensor ``Fp8TensorState``: an amax HISTORY window + the derived
  scale (``fp8_max / max(history)`` with a power-of-2 margin);
- ``quantize``/``dequantize`` into jax's real fp8 dtypes
  (``float8_e4m3fn`` forward, ``float8_e5m2`` for gradients — the
  standard hybrid format split: e4m3's precision for activations/weights,
  e5m2's range for grads);
- ``fp8_dense``: a linear layer whose operands pass through
  quantize->dequantize with DELAYED scales (the current step quantizes
  with the PREVIOUS steps' statistics — that is the entire point of the
  recipe: no dependency of this step's matmul on this step's amax), and
  whose amaxes are synchronized over the mesh's amax group
  (``parallel_state.amax_reduction``: dp x cp x tp, every rank holding a
  shard of the same activations) before entering the history.

The matmul itself runs in the compute dtype after dequantization (QDQ).
On hardware whose MXU consumes fp8 directly XLA may fuse the dequant into
the dot; the recipe state machine — what the reference's amax groups
exist to serve — is identical either way, and it is what the tests pin.
"""

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "FP8_MAX",
    "Fp8TensorState",
    "init_fp8_state",
    "update_fp8_state",
    "quantize",
    "dequantize",
    "fp8_dense",
]

# largest finite magnitudes of the two OCP fp8 formats
FP8_MAX = {
    "e4m3": 448.0,
    "e5m2": 57344.0,
}
_DTYPES = {
    "e4m3": jnp.float8_e4m3fn,
    "e5m2": jnp.float8_e5m2,
}


class Fp8TensorState(NamedTuple):
    """Delayed-scaling state of ONE tensor role (x, weight, or grad)."""

    amax_history: jax.Array  # (history_len,) fp32, most recent at [0]
    scale: jax.Array  # () fp32, applied BEFORE casting to fp8


def init_fp8_state(history_len: int = 16) -> Fp8TensorState:
    return Fp8TensorState(
        amax_history=jnp.zeros((history_len,), jnp.float32),
        scale=jnp.ones((), jnp.float32),
    )


def update_fp8_state(
    state: Fp8TensorState, amax_new, fmt: str = "e4m3", margin: int = 0
) -> Fp8TensorState:
    """Push ``amax_new`` into the history and re-derive the scale from the
    window maximum: ``scale = 2^-margin * fp8_max / amax``.  A zero window
    (nothing observed yet) keeps scale 1 rather than dividing by zero."""
    hist = jnp.roll(state.amax_history, 1).at[0].set(
        jnp.asarray(amax_new, jnp.float32)
    )
    amax = jnp.max(hist)
    scale = jnp.where(
        amax > 0.0,
        (2.0 ** (-margin)) * FP8_MAX[fmt] / amax,
        jnp.ones((), jnp.float32),
    )
    return Fp8TensorState(amax_history=hist, scale=scale)


def quantize(x, scale, fmt: str = "e4m3"):
    """x -> fp8 with saturation: clamp(x*scale, ±fp8_max).astype(fp8)."""
    lim = FP8_MAX[fmt]
    return jnp.clip(
        x.astype(jnp.float32) * scale, -lim, lim
    ).astype(_DTYPES[fmt])


def dequantize(qx, scale, dtype=jnp.float32):
    return (qx.astype(jnp.float32) / scale).astype(dtype)


def _synced_amax(x):
    """|x| max, reduced over the mesh's amax group when one is live (the
    reference's raison d'être for its amax process groups)."""
    from apex_tpu.parallel import parallel_state

    return parallel_state.amax_reduction(
        jnp.max(jnp.abs(x)).astype(jnp.float32)
    )


def fp8_dense(
    x,
    w,
    state_x: Fp8TensorState,
    state_w: Fp8TensorState,
    bias=None,
    fmt: str = "e4m3",
    margin: int = 0,
    compute_dtype=jnp.float32,
) -> Tuple[jax.Array, Tuple[Fp8TensorState, Fp8TensorState]]:
    """``y = dequant(q(x)) @ dequant(q(w)) (+ bias)`` with DELAYED scales.

    Quantization uses the scales carried in ``state_x``/``state_w`` — i.e.
    statistics from previous steps — while THIS step's (amax-group-synced)
    amaxes only enter the returned states.  Returns ``(y, (state_x',
    state_w'))``; thread the states through the train loop like optimizer
    state.
    """
    qx = quantize(x, state_x.scale, fmt)
    qw = quantize(w, state_w.scale, fmt)
    y = jnp.dot(
        dequantize(qx, state_x.scale, compute_dtype),
        dequantize(qw, state_w.scale, compute_dtype),
    )
    if bias is not None:
        y = y + bias.astype(y.dtype)
    new_x = update_fp8_state(state_x, _synced_amax(x), fmt, margin)
    new_w = update_fp8_state(state_w, _synced_amax(w), fmt, margin)
    return y, (new_x, new_w)
