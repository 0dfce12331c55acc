"""Model-parallel-aware grad scaler.

Reference parity: apex/transformer/amp/grad_scaler.py — a GradScaler whose
found_inf is all-reduced across the model-parallel group so every TP/PP rank
skips (or steps) together.

TPU design: under shard_map the overflow flag is a per-shard value; ``psum``
over the model-parallel mesh axes makes the skip decision globally
consistent. Outside shard_map (pure pjit/GSPMD) the flag is already global
and the sync is a no-op.
"""

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.amp.scaler import LossScaler, LossScalerState
from apex_tpu.monitor.xray import ledger as xlax


def _axis_in_scope(name: str) -> bool:
    try:
        jax.lax.axis_index(name)
        return True
    except Exception:
        return False


class GradScaler(LossScaler):
    """LossScaler that syncs found_inf over model-parallel axes.

    ``model_parallel_axes`` defaults to ('tp', 'pp') — the model-parallel
    group of the reference (parallel_state.get_model_parallel_group()).
    """

    def __init__(self, *args, model_parallel_axes: Sequence[str] = ("tp", "pp"), **kw):
        super().__init__(*args, **kw)
        self.model_parallel_axes = tuple(model_parallel_axes)

    def sync_found_inf(self, found_inf) -> jax.Array:
        f = jnp.asarray(found_inf, jnp.float32)
        for ax in self.model_parallel_axes:
            # the psum runs even when the axis has size 1: it moves no
            # bytes (XLA elides size-1 reduces; the xray ledger doesn't
            # record them) but it DOES establish replication over the
            # axis, which checked shard_map (check_vma=True)
            # needs to type a P() out_spec — skipping it on degenerate
            # tp=1/pp=1 meshes breaks out_specs inference (verified).
            # The analysis collective.dead-traffic warning for this site
            # is allowlisted with this reason (analysis/allowlist.py).
            if _axis_in_scope(ax):
                f = xlax.psum(f, ax)
        return f > 0

    def unscale(self, state: LossScalerState, grads) -> Tuple[jax.Array, jax.Array]:
        grads, found_inf = super().unscale(state, grads)
        return grads, self.sync_found_inf(found_inf)
