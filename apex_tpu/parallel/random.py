"""Parallel RNG and activation checkpointing.

Reference parity: apex/transformer/tensor_parallel/random.py —
``CudaRNGStatesTracker`` (:124) forks CUDA RNG state per region,
``model_parallel_cuda_manual_seed`` (:204) gives TP rank i the seed
``seed + 2718 + tp_rank`` for model-parallel regions and the plain seed for
data-parallel regions, and ``CheckpointFunction`` (:237) re-runs forward in
backward with the RNG state restored.

TPU design: JAX PRNG keys are pure values — the entire stateful tracker
collapses into ``jax.random.fold_in``:

- model-parallel region key  = fold_in(fold_in(key, 2718), tp_rank)
- data-parallel region key   = key (same on all TP ranks)

and activation checkpointing is ``jax.checkpoint`` (recompute with identical
keys by construction — no fork/restore machinery needed; this is hard part
"RNG exactness" solved by design).
"""

import functools
from typing import Callable

import jax

from apex_tpu.monitor.xray import ledger as xlax
from apex_tpu.parallel import parallel_state

_MODEL_PARALLEL_OFFSET = 2718  # matches the reference's seed offset constant


def model_parallel_rng_key(key, axis_name: str = "tp"):
    """Key for model-parallel regions: distinct per TP rank.

    (ref: random.py:204-236 — tensor-model-parallel seed = seed + 2718 + rank)
    """
    key = jax.random.fold_in(key, _MODEL_PARALLEL_OFFSET)
    if parallel_state.model_parallel_is_initialized():
        if parallel_state.get_tensor_model_parallel_world_size() > 1:
            rank = jax.lax.axis_index(axis_name)
            key = jax.random.fold_in(key, rank)
    return key


def shard_aware_rng_key(key, axis_names):
    """Fold the rank along each *active* named axis into ``key``.

    Used to decorrelate dropout masks across shards that each hold a
    different slice of the same logical tensor (sequence-parallel over tp,
    context-parallel over cp) — the SPMD equivalent of the reference's
    CudaRNGStatesTracker keeping distinct generator states per
    model-parallel rank (ref: random.py:124-236). Axes that are not bound
    (module traced outside shard_map, e.g. during ``init``) or have size 1
    are skipped.
    """
    for name in axis_names:
        try:
            key = jax.random.fold_in(key, jax.lax.axis_index(name))
        except NameError:
            pass
    return key


def data_parallel_rng_key(key):
    """Key for data-parallel regions: identical on all TP ranks (ref:
    random.py — default generator keeps the data-parallel seed)."""
    return key


def model_parallel_seed(seed: int, tp_rank: int) -> int:
    """Host-side helper mirroring the reference's integer seed math, for
    tests that compare against closed-form rank seeds."""
    return seed + _MODEL_PARALLEL_OFFSET + tp_rank


def checkpoint(fn: Callable = None, *, policy=None, prevent_cse: bool = True):
    """Activation checkpointing (ref: tensor_parallel.random.checkpoint,
    random.py:237 CheckpointFunction).

    A thin alias of ``jax.checkpoint``: forward runs without saving
    intermediates; backward recomputes. ``policy`` maps to
    ``jax.checkpoint_policies`` (e.g. ``dots_saveable``) — the TPU analogue
    of the reference's ``distribute_saved_activations`` memory knobs.
    """
    if fn is None:
        return functools.partial(checkpoint, policy=policy, prevent_cse=prevent_cse)
    return jax.checkpoint(fn, policy=policy, prevent_cse=prevent_cse)


# saved-activation distribution (random.py:246-266 partitions saved tensors
# across TP ranks): on TPU, save activations sequence-sharded instead
def distribute_saved_activations_policy():
    """Checkpoint policy that offloads nothing but marks only cheap
    recomputes: save matmul outputs, recompute elementwise. The
    sequence-sharded variant comes from running the checkpointed fn under
    shard_map with SP enabled — saved residuals are then already 1/tp-sized,
    which is what the reference's distribute_saved_activations achieves."""
    return jax.checkpoint_policies.dots_saveable


def checkpoint_distributed(fn: Callable, axis_name: str = "tp"):
    """Checkpoint with the saved boundary activation PARTITIONED over the
    tensor-parallel ranks (ref random.py:246-266: CheckpointFunction with
    ``distribute_saved_activations`` splits the saved input across the TP
    group and all-gathers it before recompute).

    The wrapped function's first argument (sequence-major, replicated over
    ``axis_name`` — the SP-off case the reference targets) is scattered
    along dim 0 OUTSIDE the checkpoint boundary and gathered back inside:
    autodiff then stashes only the 1/tp shard. The memory saving costs
    three all-gathers per step (forward primal, backward recompute, and
    the scatter's cotangent transpose) — the price of (tp-1)/tp of every
    boundary. Must run inside shard_map with ``axis_name`` bound, and dim 0
    must divide by the axis size (asserted — a silent floor-split would
    drop rows).

    In compiled memory on the CPU mesh it wins when MANY segments stash
    boundaries (the per-layer remat pattern — 3.7x less live memory at 16
    segments, tp=8); for a SINGLE segment the transient all-gather buffer outweighs
    the one saved boundary (0.84x), so don't wrap a whole network in one
    call.
    """
    from apex_tpu.parallel.mappings import (
        gather_from_sequence_parallel_region,
        scatter_to_sequence_parallel_region,
    )

    inner = jax.checkpoint(
        lambda shard, *args: fn(
            gather_from_sequence_parallel_region(
                shard, axis_name, to_model_parallel=False
            ),
            *args,
        )
    )

    @functools.wraps(fn)
    def wrapped(x, *args):
        n = xlax.axis_size(axis_name)
        if x.shape[0] % n != 0:
            raise ValueError(
                f"checkpoint_distributed: leading dim ({x.shape[0]}) not "
                f"divisible by {axis_name} size ({n}); the split would "
                "silently drop rows"
            )
        return inner(scatter_to_sequence_parallel_region(x, axis_name), *args)

    return wrapped
