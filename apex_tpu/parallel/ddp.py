"""Data-parallel gradient synchronization.

Reference parity: apex.parallel.DistributedDataParallel
(parallel/distributed.py:131) and Reducer (:91). The reference implements
bucketed, multi-stream, overlapped NCCL allreduce with dynamic bucket
structure negotiation (:287-517) — roughly 600 lines of machinery whose
*entire purpose* (overlap comm with backward compute, batch small tensors)
is performed on TPU by XLA's collective scheduler given a single ``psum``
in the compiled step. What remains semantically meaningful is preserved:

- ``gradient_average`` / ``gradient_predivide_factor``: pre-divide by N
  before the sum, post-divide by N/factor after (distributed.py:439-455),
  which trades overflow headroom in fp16 grads;
- ``allreduce_always_fp32``: cast grads to fp32 around the reduce;
- param broadcast at init (distributed.py:257) — ``broadcast_params``.
"""

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from apex_tpu.monitor.xray import ledger as xlax


def vma_tracking_live(axis_name: str) -> bool:
    """Trace-time: is varying-manual-axes tracking active for this axis?
    (``check_vma=False`` turns ``pcast`` into a no-op, so the probe's
    type stays unvarying there.) Per-trace-context constant — hoist out
    of per-leaf loops."""
    probe = jax.lax.pcast(jnp.zeros(()), axis_name, to="varying")
    return axis_name in jax.typeof(probe).vma


def grads_already_reduced(x, axis_name: str, tracking: bool = None) -> bool:
    """Trace-time: is ``x`` ALREADY the cross-rank sum over ``axis_name``?

    Under jax's checked shard_map (``check_vma=True``, the default),
    ``jax.grad`` of an axis-varying loss w.r.t. axis-replicated params
    inserts the cross-rank psum in the transpose, so the grad leaf comes
    back UNVARYING — summed. Detection must be two-step because under
    ``check_vma=False`` every aval reads as unvarying while the auto-psum
    does NOT happen (grads stay per-rank local, measured in
    tests/test_ddp.py's harness): the ``vma_tracking_live`` probe tells
    whether unvarying proves anything (pass it in when calling per leaf).
    """
    if axis_name in jax.typeof(x).vma:
        return False  # genuinely per-rank varying
    if tracking is None:
        tracking = vma_tracking_live(axis_name)
    return tracking


def all_reduce_gradients(
    grads: Any,
    axis_name: str = "dp",
    gradient_average: bool = True,
    gradient_predivide_factor: float = 1.0,
    allreduce_always_fp32: bool = False,
    compression: Optional[Any] = None,
    ef_state: Optional[Any] = None,
) -> Any:
    """psum-average a grad pytree over the data-parallel axis.

    Call inside shard_map/pmap over ``axis_name`` after ``jax.grad``.

    ``compression`` (a :class:`~apex_tpu.parallel.compress
    .CompressionConfig`) replaces each classic-regime psum with the
    block-scaled quantized all-reduce of ``parallel/compress.py`` —
    gradients travel int8 (+ per-block fp32 scales) instead of
    fp32/bf16. ``ef_state`` (a matching fp32 residual pytree from
    ``compress.ef_init``) enables error feedback: when given, the
    return value is ``(grads, new_ef_state)`` instead of ``grads``.
    Non-finite grads still propagate (poisoned scales dequantize to
    NaN), so the grad scaler's found_inf consensus — which is never
    compressed — fires exactly as on the exact path. Leaves in the
    ALREADY-REDUCED regime carry no wire traffic and pass through
    compression untouched (their residual stays zero).

    TWO REGIMES, dispatched per-leaf on the varying-manual-axes type
    (``jax.typeof(g).vma``):

    - **already-reduced grads** (``axis_name`` NOT in the leaf's vma):
      under jax's checked shard_map semantics, ``jax.grad`` of a
      dp-varying loss w.r.t. dp-REPLICATED params inserts the cross-rank
      psum in the transpose automatically — the "bucketed overlapped
      allreduce" arrives for free, scheduled by XLA. The leaf is already
      the SUM over ranks, so averaging is a division by N and another
      psum would double-count (each rank would get N x the sum — the bug
      this dispatch fixes, caught by tests/test_ddp.py).
    - **per-rank local grads** (``axis_name`` in the leaf's vma — e.g.
      produced under a loss that never mixed ranks, or hand-built): the
      classic psum path, with the reference's predivide/postdivide
      ordering (distributed.py:439-455) trading fp16 overflow headroom.

    CAVEAT (differs from torch DDP): with a forward collective over
    ``axis_name`` in the loss (e.g. SyncBatchNorm), differentiate the
    GLOBAL loss — ``jax.grad(lambda p: lax.pmean(loss_fn(p), axis_name))``
    — so the cross-shard terms transpose correctly
    (tests/test_amp_convergence.py pins the patterns) — and then **skip
    this function entirely**.  Those grads arrive unvarying and ALREADY
    AVERAGED (the pmean's 1/N rides the transpose), and the unvarying
    type cannot distinguish a sum (divide by N) from a mean (already
    final): the already-reduced branch here would silently return
    mean/N.  Like ``zero_scatter_grads``, this function is ONLY for
    grads of a PER-RANK (shard-local) loss; tests/test_ddp.py pins both
    regimes.
    """
    if compression is None and ef_state is not None:
        raise ValueError(
            "ef_state without compression: the exact psum has no "
            "quantization error to feed back"
        )
    n = xlax.axis_size(axis_name)
    tracking = vma_tracking_live(axis_name)

    def _one(g, ef):
        orig = g.dtype
        if allreduce_always_fp32:
            g = g.astype(jnp.float32)
        if grads_already_reduced(g, axis_name, tracking):
            # transpose already psummed over axis_name: sum -> mean.
            # With average the predivide factor cancels exactly as in the
            # classic path ((sum/f)*(f/N) = sum/N); without it the classic
            # path returns sum/f, so divide here too for regime parity.
            if gradient_average:
                g = g / n
            elif gradient_predivide_factor != 1.0:
                g = g / gradient_predivide_factor
            return g.astype(orig), ef
        if gradient_predivide_factor != 1.0:
            g = g / gradient_predivide_factor
        if compression is not None:
            from apex_tpu.parallel import compress as _compress

            acc = g.astype(jnp.float32) if ef is None else (
                g.astype(jnp.float32) + ef
            )
            g, sent = _compress.quantized_psum(
                acc, axis_name, compression, return_transmitted=True
            )
            if ef is not None:
                ef = _compress.ef_update(acc, sent)
        else:
            g = xlax.psum(g, axis_name)
        if gradient_average:
            g = g * (gradient_predivide_factor / n)
        return g.astype(orig), ef

    if ef_state is None:
        return jax.tree_util.tree_map(lambda g: _one(g, None)[0], grads)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    ef_leaves, ef_treedef = jax.tree_util.tree_flatten(ef_state)
    if ef_treedef != treedef:
        # a positional zip over mismatched trees would silently pair
        # residuals with the WRONG gradients — corrupt error feedback,
        # not an error; build ef_state with compress.ef_init(grads)
        raise ValueError(
            f"ef_state structure {ef_treedef} does not match grads "
            f"{treedef}"
        )
    pairs = [_one(g, e) for g, e in zip(leaves, ef_leaves)]
    return (
        jax.tree_util.tree_unflatten(treedef, [p[0] for p in pairs]),
        jax.tree_util.tree_unflatten(treedef, [p[1] for p in pairs]),
    )


def broadcast_params(params: Any, axis_name: str = "dp") -> Any:
    """Make rank-0's params authoritative on every DP rank (ref:
    distributed.py:257 broadcasts at wrap time). Under shard_map:
    implemented as an all-gather-pick; under plain SPMD params are already
    replicated and this is identity."""

    def _one(p):
        gathered = xlax.all_gather(p, axis_name, axis=0)
        return gathered[0]

    return jax.tree_util.tree_map(_one, params)


class DistributedDataParallel:
    """Functional DDP wrapper.

    Wraps a ``loss_fn(params, batch) -> loss`` so that ``grad_fn`` returns
    DP-synchronized gradients. Unlike the reference there is no module to
    wrap — the object just carries the reduction options and the axis.
    """

    def __init__(
        self,
        loss_fn: Optional[Callable] = None,
        axis_name: str = "dp",
        gradient_average: bool = True,
        gradient_predivide_factor: float = 1.0,
        allreduce_always_fp32: bool = False,
        compression: Optional[Any] = None,
    ):
        self.loss_fn = loss_fn
        self.axis_name = axis_name
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.compression = compression

    def reduce(self, grads: Any, ef_state: Optional[Any] = None) -> Any:
        """Sync grads; with ``compression`` + ``ef_state`` returns
        ``(grads, new_ef_state)`` (see ``all_reduce_gradients``)."""
        return all_reduce_gradients(
            grads,
            self.axis_name,
            self.gradient_average,
            self.gradient_predivide_factor,
            self.allreduce_always_fp32,
            compression=self.compression,
            ef_state=ef_state,
        )

    def value_and_grad(self, *args, **kwargs):
        """jax.value_and_grad with the gradient allreduce fused in.

        See the ``all_reduce_gradients`` caveat: not for models whose
        forward psums over the dp axis (e.g. SyncBatchNorm) — there,
        differentiate the pmean'd global loss directly."""
        vg = jax.value_and_grad(self.loss_fn, *args, **kwargs)

        def wrapped(*a, **k):
            val, grads = vg(*a, **k)
            return val, self.reduce(grads)

        return wrapped


class Reducer:
    """Manual-sync helper (ref: parallel/distributed.py:91): user calls
    ``reduce`` explicitly, no implicit hooks. Contract: the cross-rank
    MEAN of per-rank values — a leaf already replicated over the axis
    (unvarying vma) is its own mean and passes through unchanged (a psum
    there would multiply by N)."""

    def __init__(self, axis_name: str = "dp"):
        self.axis_name = axis_name

    def reduce(self, tree: Any) -> Any:
        n = xlax.axis_size(self.axis_name)
        tracking = vma_tracking_live(self.axis_name)

        def _one(x):
            if grads_already_reduced(x, self.axis_name, tracking):
                # replicated leaf: it IS the value on every rank; but
                # Reducer's contract is a MEAN of per-rank values, and a
                # replicated leaf's mean is itself
                return x
            return xlax.psum(x, self.axis_name) / n

        return jax.tree_util.tree_map(_one, tree)
