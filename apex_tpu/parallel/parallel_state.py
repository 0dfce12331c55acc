"""Model-parallel state: the device mesh and its accessor API.

Reference parity: apex/transformer/parallel_state.py (:155
initialize_model_parallel, :266-407 group construction, :590-755 rank/world
accessors, :761 destroy). The reference builds ~10 families of NCCL process
groups (DP, TP, PP, model, embedding, position-embedding, amax, …); on TPU
*all* of them collapse into named axes of one ``jax.sharding.Mesh``:

    mesh axes = ('dp', 'pp', 'cp', 'tp')     # outermost -> innermost

- 'tp' innermost so tensor-parallel collectives ride the fastest ICI links;
- 'dp' outermost so data-parallel allreduce can cross DCN on multi-slice;
- 'cp' (context/sequence-ring parallelism) sits between — an extension over
  the reference (which has no CP; SURVEY.md §2.5).
- Megatron sequence parallelism reuses the 'tp' axis (as in the reference,
  mappings.py:213-272) and needs no axis of its own.
- The backend-selection dimension (NCCL vs UCC vs IB/Socket hybrid,
  parallel_state.py:108-153) does not exist: XLA compiles collectives onto
  ICI/DCN from the mesh layout.

Rank accessors return Python ints when the corresponding axis is unsharded
and traced values (``lax.axis_index``) inside shard_map otherwise — matching
how the reference's per-process ints generalize to SPMD.
"""

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

_MESH: Optional[Mesh] = None
_VIRTUAL_PIPELINE_WORLD_SIZE: Optional[int] = None
_VIRTUAL_PIPELINE_RANK: Optional[int] = None
_PIPELINE_SPLIT_RANK: Optional[int] = None

# canonical axis names
DATA_AXIS = "dp"
PIPELINE_AXIS = "pp"
CONTEXT_AXIS = "cp"
TENSOR_AXIS = "tp"
AXIS_ORDER = (DATA_AXIS, PIPELINE_AXIS, CONTEXT_AXIS, TENSOR_AXIS)


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
):
    """Multi-host/controller bring-up (the reference's
    ``torch.distributed.init_process_group`` role, commons.py:250 +
    parallel_state's NCCL group machinery).

    Wraps ``jax.distributed.initialize`` — with no arguments it reads the
    standard cluster environment (TPU pod metadata / COORDINATOR_ADDRESS /
    SLURM), after which ``jax.devices()`` spans every host and
    ``initialize_model_parallel`` lays the global mesh over them (dp
    outermost → DCN; tp innermost → ICI).

    Idempotent and single-process-safe by explicit checks, not exception
    matching: already-initialized returns immediately, and with no
    arguments AND no cluster environment there is nothing to coordinate,
    so the call is a no-op returning ``(process_count, process_index)``
    (jax's auto-detection would otherwise raise on a dev box).
    """
    if jax.distributed.is_initialized():
        return jax.process_count(), jax.process_index()
    cluster_env = any(
        v in os.environ
        for v in (
            "COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
            "SLURM_JOB_ID", "TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS",
        )
    )
    if coordinator_address is None and num_processes is None and not cluster_env:
        return jax.process_count(), jax.process_index()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    return jax.process_count(), jax.process_index()


def initialize_model_parallel(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    pipeline_model_parallel_split_rank: Optional[int] = None,
    context_parallel_size: int = 1,
    devices: Optional[Sequence] = None,
    num_slices: int = 1,
) -> Mesh:
    """Build the global mesh (ref: parallel_state.py:155).

    ``devices`` defaults to ``jax.devices()``; data-parallel size is whatever
    remains after tp*pp*cp, exactly like the reference computes
    data_parallel_size = world_size // (tp*pp) (parallel_state.py:241).

    Topology: with default devices, ``mesh_utils.create_device_mesh``
    arranges the axes along the physical ICI torus (the analogue of the
    reference's IB/Socket-aware NCCL group construction,
    parallel_state.py:108-153). ``num_slices > 1`` builds a HYBRID mesh for
    multi-slice/multi-host pods: the data-parallel axis is split so its
    outer factor crosses DCN while everything else stays on ICI
    (``mesh_utils.create_hybrid_device_mesh``). An explicit ``devices`` list
    (tests, sub-meshes) keeps the plain reshape.
    """
    global _MESH, _VIRTUAL_PIPELINE_WORLD_SIZE, _VIRTUAL_PIPELINE_RANK
    global _PIPELINE_SPLIT_RANK
    explicit = devices is not None
    if devices is None:
        devices = jax.devices()
    world = len(devices)
    tp, pp, cp = (
        tensor_model_parallel_size,
        pipeline_model_parallel_size,
        context_parallel_size,
    )
    if world % (tp * pp * cp) != 0:
        raise RuntimeError(
            f"world size ({world}) is not divisible by tp ({tp}) x pp ({pp}) x cp ({cp})"
        )
    dp = world // (tp * pp * cp)
    if num_slices > 1:
        if explicit:
            raise ValueError(
                "num_slices > 1 needs the full device topology; it cannot "
                "be combined with an explicit devices list"
            )
        if dp % num_slices != 0:
            raise RuntimeError(
                f"data-parallel size ({dp}) is not divisible by num_slices "
                f"({num_slices}); only dp crosses DCN"
            )
        from jax.experimental import mesh_utils

        per_slice = (dp // num_slices, pp, cp, tp)
        arr = mesh_utils.create_hybrid_device_mesh(
            per_slice, (num_slices, 1, 1, 1), devices=devices
        )
    elif explicit:
        arr = np.asarray(devices).reshape(dp, pp, cp, tp)
    else:
        from jax.experimental import mesh_utils

        if devices and devices[0].platform == "cpu":
            # CPU backends carry no topology; plain order, no mesh_utils
            arr = np.asarray(devices).reshape(dp, pp, cp, tp)
        else:
            # on real hardware a failure here (unmappable factorization)
            # must surface — silently falling back to enumeration order
            # would put tp collectives on slow links with no diagnostic
            arr = mesh_utils.create_device_mesh((dp, pp, cp, tp),
                                                devices=devices)
    _MESH = Mesh(arr, AXIS_ORDER)
    _VIRTUAL_PIPELINE_WORLD_SIZE = virtual_pipeline_model_parallel_size
    _VIRTUAL_PIPELINE_RANK = 0 if virtual_pipeline_model_parallel_size else None
    _PIPELINE_SPLIT_RANK = pipeline_model_parallel_split_rank
    return _MESH


def model_parallel_is_initialized() -> bool:
    return _MESH is not None


def get_mesh() -> Mesh:
    if _MESH is None:
        raise RuntimeError("model parallel mesh is not initialized")
    return _MESH


def destroy_model_parallel() -> None:
    """(ref: parallel_state.py:761)"""
    global _MESH, _VIRTUAL_PIPELINE_WORLD_SIZE, _VIRTUAL_PIPELINE_RANK
    global _PIPELINE_SPLIT_RANK
    _MESH = None
    _VIRTUAL_PIPELINE_WORLD_SIZE = None
    _VIRTUAL_PIPELINE_RANK = None
    _PIPELINE_SPLIT_RANK = None


# -- world sizes ------------------------------------------------------------


def _axis_size(name: str) -> int:
    return int(get_mesh().shape[name])


def get_tensor_model_parallel_world_size() -> int:
    return _axis_size(TENSOR_AXIS)


def get_pipeline_model_parallel_world_size() -> int:
    return _axis_size(PIPELINE_AXIS)


def get_context_parallel_world_size() -> int:
    return _axis_size(CONTEXT_AXIS)


def get_data_parallel_world_size() -> int:
    return _axis_size(DATA_AXIS)


def get_model_parallel_world_size() -> int:
    return get_tensor_model_parallel_world_size() * get_pipeline_model_parallel_world_size()


def get_virtual_pipeline_model_parallel_world_size() -> Optional[int]:
    return _VIRTUAL_PIPELINE_WORLD_SIZE


def get_amax_reduction_axes() -> tuple:
    """Axes of the FP8 amax-reduction group (ref parallel_state.py:280-292:
    tp x dp ranks sharing a pipeline stage — every rank that sees a shard
    of the same activations; 'cp' joins for the same reason dp does).
    Use inside shard_map: ``amax = amax_reduction(local_amax)``."""
    return (DATA_AXIS, CONTEXT_AXIS, TENSOR_AXIS)


def amax_reduction(local_amax):
    """pmax of a local |activation|-max over the amax group (the delayed-
    scaling statistic FP8 recipes synchronize; ref use_fp8 groups)."""
    out = local_amax
    for ax in get_amax_reduction_axes():
        if _MESH is not None and int(get_mesh().shape[ax]) > 1:
            try:
                from apex_tpu.monitor.xray import ledger as xlax

                out = xlax.pmax(out, ax)
            except NameError as e:
                # outside shard_map the statistic would be silently
                # UNREDUCED over a >1 axis — surface the misuse instead
                raise RuntimeError(
                    f"amax_reduction over {ax!r} requested outside shard_map "
                    f"while the mesh has {int(get_mesh().shape[ax])} shards; "
                    f"the amax would miss the other shards' values. Call "
                    f"inside shard_map."
                ) from e
    return out


# -- ranks ------------------------------------------------------------------


def _axis_rank(name: str):
    """Python 0 when the axis is trivial; traced ``lax.axis_index`` inside
    shard_map over that axis.  Outside shard_map with a >1 axis there IS no
    well-defined rank (the single-controller host sees all shards), so that
    misuse raises instead of silently acting as rank 0;
    non-axis errors (bad axis name, tracing bugs) always propagate."""
    if _MESH is None or int(get_mesh().shape[name]) == 1:
        return 0
    try:
        return jax.lax.axis_index(name)
    except NameError as e:
        raise RuntimeError(
            f"{name!r} rank requested outside shard_map while the mesh has "
            f"{int(get_mesh().shape[name])} {name!r} shards — the host view "
            f"has no single rank. Call inside shard_map over {name!r}."
        ) from e


def get_tensor_model_parallel_rank():
    return _axis_rank(TENSOR_AXIS)


def get_pipeline_model_parallel_rank():
    return _axis_rank(PIPELINE_AXIS)


def get_context_parallel_rank():
    return _axis_rank(CONTEXT_AXIS)


def get_data_parallel_rank():
    return _axis_rank(DATA_AXIS)


def get_virtual_pipeline_model_parallel_rank() -> Optional[int]:
    return _VIRTUAL_PIPELINE_RANK


def set_virtual_pipeline_model_parallel_rank(rank: int) -> None:
    global _VIRTUAL_PIPELINE_RANK
    _VIRTUAL_PIPELINE_RANK = rank


def get_pipeline_model_parallel_split_rank() -> Optional[int]:
    return _PIPELINE_SPLIT_RANK


def is_pipeline_first_stage(ignore_virtual: bool = False):
    """(ref: parallel_state.py:649) — traced bool inside shard_map over pp."""
    if not ignore_virtual and _VIRTUAL_PIPELINE_WORLD_SIZE is not None:
        if _VIRTUAL_PIPELINE_RANK != 0:
            return False
    r = get_pipeline_model_parallel_rank()
    return r == 0


def is_pipeline_last_stage(ignore_virtual: bool = False):
    """(ref: parallel_state.py:660)"""
    if not ignore_virtual and _VIRTUAL_PIPELINE_WORLD_SIZE is not None:
        if _VIRTUAL_PIPELINE_RANK != (_VIRTUAL_PIPELINE_WORLD_SIZE - 1):
            return False
    r = get_pipeline_model_parallel_rank()
    return r == get_pipeline_model_parallel_world_size() - 1


# -- sharding helpers -------------------------------------------------------


def named_sharding(*spec):
    """NamedSharding over the global mesh for a PartitionSpec."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(get_mesh(), PartitionSpec(*spec))
