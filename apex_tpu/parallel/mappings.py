"""Tensor-parallel collective mappings (autograd-paired collectives).

Reference parity: apex/transformer/tensor_parallel/mappings.py — the six
autograd Functions that define Megatron TP/SP:

| reference (mappings.py)                   | forward            | backward          |
|-------------------------------------------|--------------------|-------------------|
| _CopyToModelParallelRegion (:141)         | identity           | all-reduce        |
| _ReduceFromModelParallelRegion (:159)     | all-reduce         | identity          |
| _ScatterToModelParallelRegion (:177)      | split last dim     | all-gather        |
| _GatherFromModelParallelRegion (:195)     | all-gather last    | split             |
| _ScatterToSequenceParallelRegion (:213)   | split first dim    | all-gather        |
| _GatherFromSequenceParallelRegion (:231)  | all-gather first   | reduce-scatter    |
| _ReduceScatterToSequenceParallelRegion (:253) | reduce-scatter | all-gather        |

TPU design: each is a ``jax.custom_vjp`` over ``lax`` collectives with a mesh
axis name (default 'tp'), usable inside ``shard_map``. Callers (the TP
layers) skip these entirely when the axis has size 1 — same fast path as the
reference's world_size==1 shortcuts; over a size-1 shard_map axis the
collectives themselves are also no-ops.
"""

import functools

import jax

from apex_tpu.monitor.xray import ledger as xlax
from apex_tpu.parallel.utils import pcast_varying

# -- raw collectives (axis-name-parameterized) ------------------------------
# All collectives go through the xray ledger wrappers (monitor/xray/
# ledger.py) — same primitives, plus trace-time comms accounting. Because
# every op here is a custom_vjp fwd OR bwd rule, a ledger trace of
# jax.grad captures the full TP fwd+bwd collective traffic.


def _split_along_axis(x, axis_name: str, dim: int):
    """Keep this rank's slice of dim (ref: utils.py split_tensor_along_last_dim)."""
    n = xlax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    size = x.shape[dim] // n
    return jax.lax.dynamic_slice_in_dim(x, idx * size, size, axis=dim)


def _all_gather_dim(x, axis_name: str, dim: int):
    return xlax.all_gather(x, axis_name, axis=dim, tiled=True)


def _all_gather_invariant_dim(x, axis_name: str, dim: int):
    """all_gather typed INVARIANT over ``axis_name``: every rank provably
    receives the same gathered array. Under checked shard_map the scatter
    ops' bwd rules owe a cotangent with the PRIMAL input's vma — a
    replicated activation — and the plain ``all_gather`` stays typed
    axis-varying, failing the custom_vjp typecheck (caught by the GPT
    pp x tp x sp integration under default shard_map). Same collective,
    different type; identical under ``check_vma=False``."""
    # private import: jax exposes no public invariant gather yet —
    # switch to the public API the release it appears
    from jax._src.lax.parallel import all_gather_invariant

    try:
        # no wrapper for the private invariant gather: record it under
        # the same op kind (identical bytes on the wire)
        xlax.record("all_gather", x, axis_name)
        return all_gather_invariant(x, axis_name, axis=dim, tiled=True)
    except TypeError as e:  # signature drift in a future jax release
        raise TypeError(
            "jax._src.lax.parallel.all_gather_invariant's signature "
            "changed; update _all_gather_invariant_dim in "
            "apex_tpu/parallel/mappings.py (falling back to the plain "
            "gather would silently lose the invariant typing checked "
            f"shard_map requires): {e}"
        ) from e


def _reduce_scatter_dim(x, axis_name: str, dim: int):
    return xlax.psum_scatter(x, axis_name, scatter_dimension=dim, tiled=True)


def _typed_gather(g, primal_probe, axis_name: str, dim: int):
    """all_gather for a scatter op's bwd, typed to match the PRIMAL:
    the usual replicated primal needs the invariant gather (checked
    shard_map owes an invarying cotangent), but a genuinely axis-varying
    primal — recorded as a zero-size residual slice carrying its vma —
    needs the plain varying gather. check_vma=False reads everything
    unvarying AND accepts either, so plain gather is used."""
    if axis_name in jax.typeof(primal_probe).vma:
        return _all_gather_dim(g, axis_name, dim)
    from apex_tpu.parallel.ddp import vma_tracking_live

    if not vma_tracking_live(axis_name):
        return _all_gather_dim(g, axis_name, dim)
    return _all_gather_invariant_dim(g, axis_name, dim)


# -- custom_vjp pairs -------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tensor_model_parallel_region(x, axis_name="tp"):
    return x


def _copy_fwd(x, axis_name):
    return x, None


def _copy_bwd(axis_name, _, g):
    return (xlax.psum(g, axis_name),)


copy_to_tensor_model_parallel_region.defvjp(_copy_fwd, _copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tensor_model_parallel_region(x, axis_name="tp"):
    return xlax.psum(x, axis_name)


def _reduce_fwd(x, axis_name):
    return xlax.psum(x, axis_name), None


def _reduce_bwd(axis_name, _, g):
    # the primal input was axis-VARYING (per-rank partial sums); the
    # cotangent of the psum'd output arrives invarying, so re-type it
    # (identity under check_vma=False, and on numerics)
    return (pcast_varying(g, axis_name),)


reduce_from_tensor_model_parallel_region.defvjp(_reduce_fwd, _reduce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def scatter_to_tensor_model_parallel_region(x, axis_name="tp"):
    return _split_along_axis(x, axis_name, -1)


def _scatter_fwd(x, axis_name):
    # zero-size slice: carries the primal's vma TYPE into bwd for free
    return _split_along_axis(x, axis_name, -1), x[..., :0]


def _scatter_bwd(axis_name, res, g):
    return (_typed_gather(g, res, axis_name, g.ndim - 1),)


scatter_to_tensor_model_parallel_region.defvjp(_scatter_fwd, _scatter_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def gather_from_tensor_model_parallel_region(x, axis_name="tp"):
    return _all_gather_dim(x, axis_name, x.ndim - 1)


def _gather_fwd(x, axis_name):
    return _all_gather_dim(x, axis_name, x.ndim - 1), None


def _gather_bwd(axis_name, _, g):
    return (_split_along_axis(g, axis_name, g.ndim - 1),)


gather_from_tensor_model_parallel_region.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def scatter_to_sequence_parallel_region(x, axis_name="tp"):
    return _split_along_axis(x, axis_name, 0)


def _scatter_seq_fwd(x, axis_name):
    return _split_along_axis(x, axis_name, 0), x[:0]


def _scatter_seq_bwd(axis_name, res, g):
    return (_typed_gather(g, res, axis_name, 0),)


scatter_to_sequence_parallel_region.defvjp(_scatter_seq_fwd, _scatter_seq_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def gather_from_sequence_parallel_region(
    x, axis_name="tp", to_model_parallel=True, defer_sync=False
):
    """SP activation gather (fwd all-gather over the sequence dim).

    ``defer_sync=True`` is the EXPERIMENTAL arXiv:2506.19645 relaxation
    (Tensor-Parallelism with Partially Synchronized Activations), off by
    default: the backward pass SKIPS the cross-rank reduce-scatter and
    keeps only the local shard of the cotangent — the gradient
    synchronization this gather owes is deferred to the surrounding dp
    sync instead of paid per-layer on the tp axis. Gradients become
    approximate (cross-rank activation-grad terms are dropped), so this
    is only sound for syncs the paper's analysis shows are relaxable;
    convergence must be re-pinned per model. The skipped collective is
    neither executed nor ledger-predicted, so the hlo-comms differ stays
    clean either way.
    """
    return _all_gather_dim(x, axis_name, 0)


def _gather_seq_fwd(x, axis_name, to_model_parallel, defer_sync):
    return _all_gather_dim(x, axis_name, 0), None


def _gather_seq_bwd(axis_name, to_model_parallel, defer_sync, _, g):
    if to_model_parallel and not defer_sync:
        return (_reduce_scatter_dim(g, axis_name, 0),)
    # defer_sync relaxation (or plain data movement): local shard only,
    # no cross-rank reduction — zero tp-axis bytes in the backward
    return (_split_along_axis(g, axis_name, 0),)


gather_from_sequence_parallel_region.defvjp(_gather_seq_fwd, _gather_seq_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_scatter_to_sequence_parallel_region(x, axis_name="tp"):
    return _reduce_scatter_dim(x, axis_name, 0)


def _rs_fwd(x, axis_name):
    return _reduce_scatter_dim(x, axis_name, 0), None


def _rs_bwd(axis_name, _, g):
    return (_all_gather_dim(g, axis_name, 0),)


reduce_scatter_to_sequence_parallel_region.defvjp(_rs_fwd, _rs_bwd)
