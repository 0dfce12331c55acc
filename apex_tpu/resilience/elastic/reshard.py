"""Elastic restore: load a checkpoint saved on mesh A onto any mesh B.

A preempted 8-chip job must be able to resume on 4 (or 16) chips without
a human re-slicing checkpoints. Two distinct problems hide under that
sentence:

- **Plain re-layout.** Params and replicated scalars keep their global
  shape across a topology change; restoring them is "read the global
  array, ``device_put`` it under the NEW mesh's ``NamedSharding``".
  Orbax can do this implicitly, but implicitly is the problem — it will
  happily lay bytes out under whatever sharding it is handed, right or
  wrong. Here the manifest's topology block (topology.py) is checked
  leaf-by-leaf first, and any mismatch it cannot *prove* resharddable is
  refused with a reasoned error instead of guessed at.
- **ZeRO regrouping.** The ZeRO flat optimizer buffers
  (``DistributedFusedAdamState``: master shard + Adam moments) bake the
  dp size into their global LENGTH — the flat param vector is
  zero-padded to a multiple of dp before sharding. Changing dp changes
  the padded length, so the restore must un-shard to the global flat
  buffer, strip/extend the zero padding to the NEW dp's padded length,
  and re-shard under the new ``zero_state_specs`` layout
  (``optimizers.zero_regroup_flat``). Only leaves the topology block
  marks ``zero_shard_axis`` may change shape this way; truncation that
  would drop a NONZERO value refuses — that is state, not padding.

Integrity survives the trip: the step directory's file digests are
verified first (the PR-1 manifest), and each restored leaf's crc32 is
checked against the save-time fingerprint on the HOST global array —
i.e. on exactly the bytes that get resharded — before any
``device_put``. A checkpoint whose newest step predates the topology
block (a pre-upgrade manifest) is skipped with a warning and the walk
falls back to the newest step that carries one; spec/shape mismatches on
a topology-bearing step are a hard :class:`ElasticRestoreError` (older
steps would mismatch the same way — refusing beats silently resuming
stale state).
"""

import logging
import os
from typing import Any, List, Optional, Tuple

import numpy as np

from apex_tpu.resilience import integrity
from apex_tpu.resilience.elastic.topology import mesh_axes
from apex_tpu.utils.checkpoint import finalized_steps

__all__ = [
    "ElasticRestoreError",
    "derive_mesh",
    "needs_reshard",
    "restore_resharded",
]

logger = logging.getLogger("apex_tpu.resilience.elastic")


class ElasticRestoreError(RuntimeError):
    """A checkpoint/target layout mismatch the elastic restore refuses to
    guess through. Deliberately NOT a ``ValueError``: callers that treat
    ``ValueError`` as "incompatible old checkpoint, start fresh" (the
    gpt example) must still crash loudly on a refused reshard."""


def _step_dir(directory: str, step: int) -> str:
    return os.path.join(os.path.abspath(directory), f"step_{step}")


def needs_reshard(directory: str, mesh, step: Optional[int] = None
                  ) -> Optional[bool]:
    """Does the newest verified checkpoint's topology differ from ``mesh``?

    Returns ``None`` when undecidable (no checkpoint, or the newest
    verified one predates the topology block), else a bool comparing the
    recorded mesh axes/device count against the live mesh. ``AutoResume``
    routes restore through :func:`restore_resharded` on ``True``.
    """
    steps = [step] if step is not None else list(
        reversed(finalized_steps(directory)))
    for s in steps:
        sd = _step_dir(directory, s)
        ok, _ = integrity.verify_checkpoint(sd, deep=False)
        if not ok:
            continue
        topo = (integrity.read_manifest(sd) or {}).get("topology")
        if not topo or not topo.get("mesh"):
            return None
        saved = topo["mesh"]
        return (saved.get("axes") != mesh_axes(mesh)
                or saved.get("devices") != int(np.asarray(mesh.devices).size))
    return None


def _plain_key(entry):
    """A jax key-path entry's key in orbax's serialized-container form
    (serialize_tree: dicts stay dicts, NamedTuples/dataclasses become
    dicts keyed by field name, sequences become lists)."""
    if hasattr(entry, "key"):
        return entry.key     # DictKey
    if hasattr(entry, "name"):
        return entry.name    # GetAttrKey
    if hasattr(entry, "idx"):
        return entry.idx     # SequenceKey
    return None


def _prune_to(plain, plain_target) -> None:
    """Drop entries of the restored ``plain`` containers absent from
    ``plain_target`` (checkpoint-only advisory EF leaves), in place."""
    if isinstance(plain, dict) and isinstance(plain_target, dict):
        for k in list(plain):
            if k not in plain_target:
                plain.pop(k)
            else:
                _prune_to(plain[k], plain_target[k])


def _host_restore(directory: str, step: int, target: Any,
                  fill: Optional[dict] = None,
                  drop_extra: bool = False) -> Any:
    """The checkpoint's GLOBAL arrays as host numpy, in ``target``'s
    structure. Explicit ``restore_type=np.ndarray`` per leaf: orbax's
    default path re-applies the sharding recorded in the checkpoint,
    which is exactly wrong across a topology change.

    The compression-toggle migration hooks (advisory EF leaves only —
    the caller validates): ``fill`` maps jax key-path tuples to host
    arrays for TARGET leaves the checkpoint does not carry (compression
    newly ON) — those entries are pruned from the restore request and
    the arrays spliced back in; ``drop_extra`` restores over the
    CHECKPOINT's own structure and prunes leaves the target does not
    want (compression turned OFF) — orbax refuses a request tree
    missing an on-disk entry, so the subset must be cut after the read."""
    import orbax.checkpoint as ocp
    from orbax.checkpoint.utils import deserialize_tree, serialize_tree
    import jax

    plain_target = serialize_tree(target, keep_empty_nodes=True)
    spliced = []
    for path, arr in (fill or {}).items():
        keys = [_plain_key(k) for k in path]
        node = plain_target
        for k in keys[:-1]:
            node = node[k]
        node.pop(keys[-1])
        spliced.append((keys, arr))
    ckptr = ocp.PyTreeCheckpointer()
    # the checkpoint's own structure comes from its metadata; orbax wraps
    # that tree in a StepMetadata whose ``item_metadata.tree`` is the plain
    # nested dict restore_args must mirror leaf for leaf
    args_tree = (
        ckptr.metadata(_step_dir(directory, step)).item_metadata.tree
        if drop_extra else plain_target
    )
    restore_args = jax.tree_util.tree_map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray), args_tree
    )
    plain = ckptr.restore(
        _step_dir(directory, step), restore_args=restore_args
    )
    if drop_extra:
        _prune_to(plain, plain_target)
    for keys, arr in spliced:
        node = plain
        for k in keys[:-1]:
            node = node[k]
        node[keys[-1]] = arr
    return deserialize_tree(plain, target, keep_empty_nodes=True)


def _target_specs_flat(target, target_specs) -> List[Any]:
    """One PartitionSpec per target leaf (caller-supplied pytree, or
    derived from each leaf's own NamedSharding; replicated otherwise)."""
    import jax
    from jax.sharding import PartitionSpec

    if target_specs is not None:
        specs = jax.tree_util.tree_leaves(
            target_specs,
            is_leaf=lambda x: x is None or isinstance(x, PartitionSpec),
        )
        return [PartitionSpec() if s is None else s for s in specs]
    specs = []
    for leaf in jax.tree_util.tree_leaves(target):
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, jax.sharding.NamedSharding):
            specs.append(sharding.spec)
        else:
            specs.append(PartitionSpec())
    return specs


def derive_mesh(target):
    """The mesh of the first NamedSharding-carrying leaf (None if none)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(target):
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, jax.sharding.NamedSharding):
            return sharding.mesh
    return None


def _check_spec_fits(path: str, shape, spec, axes: dict) -> None:
    """Refuse specs naming absent axes, outranking the leaf, or not
    dividing its dims — checked BEFORE any device_put so every refusal
    is an :class:`ElasticRestoreError` with the reason, not a jax
    sharding error soup."""
    entries = tuple(spec)
    for entry in entries:
        if entry is None:
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        for name in names:
            if name not in axes:
                raise ElasticRestoreError(
                    f"leaf {path}: target spec {spec} names mesh axis "
                    f"{name!r} absent from the restore mesh (axes {axes})"
                )
    if len(entries) > len(shape):
        raise ElasticRestoreError(
            f"leaf {path}: target spec {spec} has more entries than the "
            f"leaf has dims (shape {tuple(shape)})"
        )
    for dim, entry in zip(shape, entries):
        if entry is None:
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        total = 1
        for name in names:
            total *= axes[name]
        if dim % total != 0:
            raise ElasticRestoreError(
                f"leaf {path}: dim {dim} not divisible by the product "
                f"{total} of mesh axes {names} (spec {spec})"
            )


def _reshard_step(directory: str, step: int, target: Any, mesh,
                  specs_flat: List[Any], topology: dict) -> Any:
    import jax
    from jax.sharding import NamedSharding

    from apex_tpu.optimizers import zero_regroup_flat

    axes = mesh_axes(mesh)
    target_paths = jax.tree_util.tree_flatten_with_path(target)[0]
    topo_leaves = topology.get("leaves", [])
    got = [jax.tree_util.keystr(p) for p, _ in target_paths]
    want = [l["path"] for l in topo_leaves]
    ef_fill: dict = {}
    if got != want:
        from apex_tpu.resilience.elastic.topology import is_ef_path

        extra = sorted(set(got) - set(want))
        missing = sorted(set(want) - set(got))
        # migration shim across the compression toggle, BOTH directions
        # (EF state is advisory — never refuse over it, topology.py):
        # target-only EF leaves (compression newly ON; pre-upgrade
        # checkpoint) are zero-filled, checkpoint-only EF leaves
        # (compression turned OFF) are simply not restored — the
        # target-driven orbax restore never reads them. Any non-EF
        # structure diff still refuses. Zero-fill needs dict/attr-keyed
        # leaves (orbax's serialized form; a list-final key's pop/splice
        # would shift sibling indices), so that case refuses too.
        ok_shim = (
            (extra or missing)
            and all(is_ef_path(p) for p in extra)
            and all(is_ef_path(p) for p in missing)
        )
        if ok_shim and extra:
            fill = {}
            for path_key, tgt_leaf in target_paths:
                p = jax.tree_util.keystr(path_key)
                if p not in extra:
                    continue
                if hasattr(path_key[-1], "idx"):
                    ok_shim = False
                    break
                fill[path_key] = np.zeros(
                    tuple(np.shape(tgt_leaf)),
                    np.dtype(getattr(tgt_leaf, "dtype", np.float32)),
                )
            ef_fill = fill if ok_shim else {}
        if not ok_shim:
            raise ElasticRestoreError(
                f"step_{step}: restore target structure differs from the "
                f"saved topology (target-only leaves {extra[:3]}, "
                f"checkpoint-only leaves {missing[:3]}) — a state-layout "
                f"change needs a migration, not a reshard"
            )
        if extra:
            logger.warning(
                "elastic restore step_%d: checkpoint predates the "
                "compressed-collective EF state; zero-filling advisory "
                "residual leaves %s", step, extra)
        if missing:
            logger.warning(
                "elastic restore step_%d: checkpoint carries EF residual "
                "leaves %s the (compression-off) target does not — "
                "advisory state, not restored", step, missing)

    manifest = integrity.read_manifest(_step_dir(directory, step)) or {}
    fp = manifest.get("fingerprint") or {}
    fp_crc = {l["path"]: l["crc32"] for l in fp.get("leaves", [])}
    topo_by_path = {l["path"]: l for l in topo_leaves}

    host = _host_restore(directory, step, target, fill=ef_fill,
                         drop_extra=bool(set(want) - set(got)))
    host_flat = jax.tree_util.tree_leaves(host)
    out_flat = []
    for (path_key, tgt_leaf), host_arr, spec in zip(
            target_paths, host_flat, specs_flat):
        path = jax.tree_util.keystr(path_key)
        arr = np.asarray(host_arr)
        topo = topo_by_path.get(path)
        if topo is None:
            # zero-filled advisory EF leaf (pre-compression checkpoint):
            # nothing on disk to verify — ship the zeros
            _check_spec_fits(path, arr.shape, spec, axes)
            out_flat.append(jax.device_put(arr, NamedSharding(mesh, spec)))
            continue
        saved_shape = tuple(topo["shape"])
        if arr.shape != saved_shape or str(arr.dtype) != topo["dtype"]:
            raise ElasticRestoreError(
                f"leaf {path}: restored bytes are {arr.dtype}{arr.shape} "
                f"but the manifest recorded {topo['dtype']}{saved_shape} — "
                f"checkpoint and manifest disagree; refusing"
            )
        tgt_shape = tuple(np.shape(tgt_leaf))
        tgt_dtype = str(getattr(tgt_leaf, "dtype", np.asarray(tgt_leaf).dtype))
        if tgt_dtype != topo["dtype"]:
            raise ElasticRestoreError(
                f"leaf {path}: target dtype {tgt_dtype} != saved dtype "
                f"{topo['dtype']} — dtype migration is not a reshard"
            )
        # crc32 on the HOST global array — the exact bytes being resharded
        # (device_put does not change values); for regrouped ZeRO leaves
        # this is the PRE-regroup buffer, i.e. the fingerprinted one
        if path in fp_crc:
            import binascii

            crc = binascii.crc32(np.ascontiguousarray(arr).tobytes())
            if crc != fp_crc[path]:
                raise ElasticRestoreError(
                    f"leaf {path}: crc32 mismatch against the save-time "
                    f"fingerprint ({crc} != {fp_crc[path]}) — restored "
                    f"bytes differ from the state that was saved"
                )
        if tgt_shape != saved_shape:
            if topo.get("ef"):
                # error-feedback residual (topology.py docstring): the
                # compressed-collective residual is ADVISORY — regroup it
                # like a ZeRO flat buffer when the length change is
                # padding-only, otherwise reset to zero with a warning.
                # NEVER a refusal: one step of re-accumulated
                # quantization error beats a dead restore. (The common
                # dp-change case IS a reset: per-rank residuals
                # concatenate over dp, so the global length change is
                # not padding-only.)
                if arr.ndim == 1 and len(tgt_shape) == 1:
                    try:
                        arr = zero_regroup_flat(arr, int(tgt_shape[0]))
                    except ValueError as e:
                        logger.warning(
                            "elastic restore: EF residual %s not "
                            "regroupable (%s); resetting to zero — the "
                            "compressed path re-accumulates it", path, e)
                        arr = np.zeros(tgt_shape, arr.dtype)
                else:
                    logger.warning(
                        "elastic restore: EF residual %s shape changed "
                        "%s -> %s; resetting to zero — the compressed "
                        "path re-accumulates it", path, saved_shape,
                        tgt_shape)
                    arr = np.zeros(tgt_shape, arr.dtype)
                _check_spec_fits(path, arr.shape, spec, axes)
                out_flat.append(jax.device_put(arr, NamedSharding(mesh, spec)))
                continue
            if topo.get("zero_shard_axis") is None or arr.ndim != 1:
                raise ElasticRestoreError(
                    f"leaf {path}: global shape changed "
                    f"{saved_shape} -> {tgt_shape} but the leaf is not a "
                    f"ZeRO flat shard buffer (no zero_shard_axis in the "
                    f"manifest) — refusing to guess a re-layout"
                )
            if len(tgt_shape) != 1:
                raise ElasticRestoreError(
                    f"leaf {path}: ZeRO regroup target must stay 1-D, "
                    f"got {tgt_shape}"
                )
            # the length change must be explainable as padding ONE common
            # unpadded length T to each side's shard-axis multiple:
            # pad_old(T) == saved_len and pad_new(T) == tgt_len for some
            # T, i.e. the two half-open T-ranges intersect. The
            # zero_shard_axis marker is a layout heuristic — without
            # this guard a genuinely GROWN 1-D sharded buffer (a resized
            # stats table, not ZeRO padding) would be silently
            # zero-extended instead of refused.
            old_axis = topo["zero_shard_axis"]
            old_size = (((topology.get("mesh") or {}).get("axes") or {})
                        .get(old_axis))
            new_size = 1
            entries = tuple(spec)
            if entries and entries[0] is not None:
                names = ((entries[0],) if isinstance(entries[0], str)
                         else tuple(entries[0]))
                for name in names:
                    new_size *= axes.get(name, 1)
            saved_len, tgt_len = saved_shape[0], int(tgt_shape[0])
            if old_size is None or (
                    max(tgt_len - new_size, saved_len - old_size)
                    >= min(tgt_len, saved_len)):
                raise ElasticRestoreError(
                    f"leaf {path}: length change {saved_len} -> {tgt_len} "
                    f"is not explainable as re-padding one unpadded "
                    f"length to the shard axis (saved axis {old_axis!r} "
                    f"size {old_size}, target shard size {new_size}) — a "
                    f"grown/shrunk buffer is a migration, not a ZeRO "
                    f"regroup"
                )
            try:
                arr = zero_regroup_flat(arr, int(tgt_shape[0]))
            except ValueError as e:
                raise ElasticRestoreError(f"leaf {path}: {e}") from e
        _check_spec_fits(path, arr.shape, spec, axes)
        out_flat.append(jax.device_put(arr, NamedSharding(mesh, spec)))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(target), out_flat
    )


def restore_resharded(
    directory: str,
    target: Any,
    mesh=None,
    target_specs: Any = None,
    step: Optional[int] = None,
    deep: bool = True,
) -> Tuple[int, Any]:
    """Restore the newest verified checkpoint onto ``target``'s topology.

    ``target`` is the freshly-initialized state on the NEW mesh — its
    leaves define the wanted global shapes/dtypes and (through their
    ``NamedSharding``s) the wanted layout. ``mesh``/``target_specs``
    override the derived mesh / per-leaf PartitionSpecs (``target_specs``
    is a matching pytree of ``PartitionSpec``/None). ``step`` pins one
    step instead of walking newest-first.

    Walk semantics: steps failing FILE verification (torn/corrupt) are
    skipped like ``load_checkpoint_verified``; verified steps whose
    manifest predates the topology block are skipped with a warning (the
    rollback-past-a-format-upgrade rule); the first topology-bearing
    verified step is restored — and any mismatch there raises
    :class:`ElasticRestoreError` rather than walking further (older
    steps share the layout; silently resuming staler state is worse
    than stopping). Raises ``FileNotFoundError`` when no checkpoint
    exists at all.
    """
    if mesh is None:
        mesh = derive_mesh(target)
    if mesh is None:
        raise ElasticRestoreError(
            "restore_resharded needs a mesh: pass mesh= or give the "
            "target leaves NamedShardings"
        )
    specs_flat = _target_specs_flat(target, target_specs)
    candidates = [step] if step is not None else list(
        reversed(finalized_steps(directory)))
    if not candidates:
        raise FileNotFoundError(f"no checkpoints under {directory}")
    pre_topology = []
    for s in candidates:
        sd = _step_dir(directory, s)
        ok, reason = integrity.verify_checkpoint(sd, deep=deep)
        if not ok:
            logger.warning(
                "elastic restore skipping unverified step_%d: %s", s, reason)
            continue
        topo = (integrity.read_manifest(sd) or {}).get("topology")
        if not topo:
            logger.warning(
                "elastic restore skipping step_%d: manifest predates the "
                "topology block (pre-upgrade checkpoint); falling back to "
                "an older step that carries one", s)
            pre_topology.append(s)
            continue
        restored = _reshard_step(directory, s, target, mesh, specs_flat, topo)
        return s, restored
    raise ElasticRestoreError(
        f"no topology-bearing verified checkpoint under {directory} "
        f"(steps considered: {candidates}; verified-but-pre-topology: "
        f"{pre_topology}) — cannot reshard without the saved layout"
    )
