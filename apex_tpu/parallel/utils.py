"""Tensor-parallel utilities.

Reference parity: apex/transformer/tensor_parallel/utils.py
(split_tensor_along_last_dim :22, VocabUtility :46) and
tensor_parallel/data.py (broadcast_data :80).
"""

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


def split_tensor_along_last_dim(x, num_partitions: int) -> Sequence[jax.Array]:
    """(ref: utils.py:22)"""
    return jnp.split(x, num_partitions, axis=-1)


class VocabUtility:
    """Vocab range math (ref: utils.py:46)."""

    @staticmethod
    def vocab_range_from_per_partition_vocab_size(
        per_partition_vocab_size: int, rank, world_size: int
    ) -> Tuple:
        start = rank * per_partition_vocab_size
        return start, start + per_partition_vocab_size

    @staticmethod
    def vocab_range_from_global_vocab_size(
        global_vocab_size: int, rank, world_size: int
    ) -> Tuple:
        per = global_vocab_size // world_size
        return VocabUtility.vocab_range_from_per_partition_vocab_size(
            per, rank, world_size
        )


def broadcast_data(keys, data, dtype=None):
    """(ref: data.py:80) — broadcast batch data from TP rank 0.

    Under single-controller SPMD every device already sees the same host
    arrays, so this is an identity kept for API parity; multi-controller
    setups get consistency from feeding identical per-process data (the
    jax.distributed contract).
    """
    del dtype
    return {k: data[k] for k in keys}


def pcast_varying(x, axis_names):
    """Type ``x`` varying over ``axis_names``, casting only the axes it
    does not already vary over: ``jax.lax.pcast(..., to='varying')``
    refuses a varying -> varying cast, and callers (error-feedback
    residuals, scan carries) cannot know which axes a value picked up on
    the way in."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    missing = tuple(a for a in axis_names if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def promote_to_vma(tree, like):
    """pcast each leaf of ``tree`` to ALSO vary over ``like``'s varying
    axes — the scan-carry fixed-point helper: accumulators must start
    with the vma their loop bodies will produce (ring attention's block
    scans derive masks from rank positions, so outputs vary even when
    inputs are replicated). No-op when already varying or under
    ``check_vma=False``."""
    want = tuple(sorted(jax.typeof(like).vma))
    if not want:
        return tree
    return jax.tree_util.tree_map(lambda x: pcast_varying(x, want), tree)


def pvary_params(tree, axis_name: str = "tp"):
    """Type every leaf of a param pytree VARYING over ``axis_name``
    (leaves already varying pass through; numerics unchanged; no-op under
    ``check_vma=False``).

    Why this exists: under jax's checked shard_map, a tensor-parallel
    param created IN-BODY with a rank-independent initializer (the
    canonical zeros bias of ColumnParallelLinear) is typed replicated
    even though each rank's slice is a distinct coordinate of the global
    parameter — and ``jax.grad`` then auto-psums its gradient over
    ``axis_name``, silently summing what should stay per-rank
    (tests/test_checked_vma.py pins the 7.5% grad error this produced).
    Params that enter the shard_map through tp-sharded ``in_specs``, or
    whose init folds in the tp rank, are already varying and unaffected.
    Call this on stage/layer param trees built inside shard_map before
    differentiating.

    ONLY for sharded params: a genuinely REPLICATED parameter must stay
    invarying — e.g. ``RowParallelLinear``'s bias, which is added once
    AFTER the tp reduction; pvarying it types the layer output spuriously
    varying and shifts every downstream gradient. Apply per-subtree when
    a tree mixes both (tests/test_checked_vma.py shows the pattern).
    """

    return jax.tree_util.tree_map(
        lambda x: pcast_varying(x, axis_name), tree
    )


def vma_cond(pred, true_fn, false_fn, *operands):
    """``jax.lax.cond`` whose branch outputs are pcast to their per-leaf
    JOIN vma, so branches varying over different manual-axis sets
    typecheck under jax's checked ``shard_map``.

    Checked mode types every value with its varying-manual-axes (vma)
    set, and ``lax.cond`` requires the two branch output types to match
    EXACTLY — which natural code frequently violates: the canonical
    "skip the optimizer step on overflow" cond returns the (replicated)
    old state from one branch and grad-varying new state from the other.
    A ``jnp.where`` select sidesteps the typecheck (selects auto-pvary)
    but evaluates BOTH branches; this wrapper keeps cond's single-branch
    evaluation by eval_shaping both branches (trace only, no compute),
    taking each output leaf's vma union, and widening each branch's
    outputs to that join INSIDE the branch.

    Falls back to plain ``lax.cond`` when nothing needs widening — in
    particular under ``check_vma=False`` and outside ``shard_map``, where
    it is exactly ``jax.lax.cond``.
    """
    try:
        # muted: these shape probes re-trace branch Python (possibly
        # containing collectives) without becoming part of the program —
        # the xray comms ledger must not double-count them
        from apex_tpu.monitor.xray import ledger as _xlax

        with _xlax.muted():
            t_shape = jax.eval_shape(true_fn, *operands)
            f_shape = jax.eval_shape(false_fn, *operands)
        t_leaves, t_def = jax.tree_util.tree_flatten(t_shape)
        f_leaves, f_def = jax.tree_util.tree_flatten(f_shape)
        if t_def != f_def or len(t_leaves) != len(f_leaves):
            # mismatched structures: let lax.cond produce its own error
            return jax.lax.cond(pred, true_fn, false_fn, *operands)
        wants = []
        any_cast = False
        for a, b in zip(t_leaves, f_leaves):
            va, vb = getattr(a, "vma", None), getattr(b, "vma", None)
            if va is None or vb is None:
                wants.append(None)
                continue
            union = set(va) | set(vb)
            wants.append(tuple(sorted(union)))
            if union != set(va) or union != set(vb):
                any_cast = True
    except Exception:
        # eval_shape failing here says nothing cond itself won't say better
        return jax.lax.cond(pred, true_fn, false_fn, *operands)
    if not any_cast:
        return jax.lax.cond(pred, true_fn, false_fn, *operands)

    def widened(fn):
        def g(*ops):
            out = fn(*ops)
            leaves, treedef = jax.tree_util.tree_flatten(out)
            leaves = [l if w is None else pcast_varying(l, w)
                      for l, w in zip(leaves, wants)]
            return jax.tree_util.tree_unflatten(treedef, leaves)

        return g

    return jax.lax.cond(pred, widened(true_fn), widened(false_fn), *operands)


def scan_carry_fixed_point(body, carry, x0, max_iters: int = 3):
    """Promote ``carry``'s leaves to the vma fixed point of ``body`` so
    ``jax.lax.scan(body, carry, xs)`` typechecks under checked shard_map.

    A training-loop carry routinely starts with narrower varying axes
    than the body produces (optimizer moments init as replicated zeros
    while their updates inherit the grads' varying axes), and checked
    scan requires carry-in type == carry-out type. This evaluates the
    body's output carry type via ``jax.eval_shape`` (trace only — no
    compute), widens the carry with ``pcast`` where needed, and repeats
    until stable (vma sets only grow toward the mesh's axis set, so this
    terminates; one round suffices in practice).

    ``x0``: one slice of the scan xs (e.g. ``tree_map(lambda a: a[0],
    xs)``); pass ``None`` for a None-xs scan. No-op under
    ``check_vma=False``. Returns the promoted carry.
    """

    # max_iters + 1 evals: a round whose widening REACHES the fixed point
    # must not raise — convergence means some eval produced no widening,
    # so the last allowed widening gets one extra verification eval
    from apex_tpu.monitor.xray import ledger as _xlax

    for _ in range(max_iters + 1):
        with _xlax.muted():  # shape probe — see vma_cond
            out_carry = jax.eval_shape(lambda c: body(c, x0)[0], carry)
        changed = False

        def widen(c, o):
            nonlocal changed
            have, want = jax.typeof(c).vma, getattr(o, "vma", None)
            if not want or not (set(want) - set(have)):
                return c
            changed = True
            return pcast_varying(c, tuple(sorted(want)))

        carry = jax.tree_util.tree_map(widen, carry, out_carry)
        if not changed:
            return carry
    raise ValueError(
        "scan_carry_fixed_point did not converge within "
        f"max_iters={max_iters} widening rounds; raise max_iters "
        "(vma sets only grow toward the mesh axis count)"
    )
