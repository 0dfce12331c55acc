"""Pipeline schedules as compiled collective programs.

Reference parity: apex/transformer/pipeline_parallel/schedules/ —
- forward_backward_no_pipelining (fwd_bwd_no_pipelining.py:23),
- 1F1B without interleaving (fwd_bwd_pipelining_without_interleaving.py:241),
- interleaved 1F1B over virtual-PP model chunks
  (fwd_bwd_pipelining_with_interleaving.py:27),
- get_forward_backward_func dispatcher (schedules/__init__.py:22),
- build_model with pre/post_process flags (schedules/common.py:30).

TPU design. The reference's schedules are host Python loops issuing dynamic
NCCL p2p ops per microbatch (warmup / steady-1F1B / cooldown phases with
wait handles). Under XLA everything inside jit is traced once and compiled,
so the schedule becomes a ``lax.scan`` over T = M + P - 1 clock ticks inside
``shard_map`` over the 'pp' mesh axis:

- at tick t, stage s computes microbatch t - s (bubble ticks compute masked
  garbage — the SPMD cost of the (P-1)/(M+P-1) pipeline bubble, identical
  to the reference's bubble fraction);
- stage edges are a single ``ppermute`` (p2p.py);
- the BACKWARD schedule is not written at all: ``jax.grad`` through the
  scan reverses it tick-for-tick (ppermute transposes into the opposite
  edge), yielding the same reversed-pipeline order the reference hand-codes
  in its cooldown/steady phases;
- 1F1B's purpose is bounding stashed activations to P microbatches; here
  per-tick ``jax.checkpoint`` on the stage body keeps live memory to the
  scan carry (one microbatch) plus per-tick boundary activations, the same
  asymptotics;
- the interleaved schedule maps virtual-PP chunk v on rank r to global
  stage v*P + r exactly like the reference's chunk-id mapping
  (fwd_bwd_pipelining_with_interleaving.py:221-259), executed as ONE scan
  over V*M + P - 1 ticks of one-chunk work each — bubble fraction
  (P-1)/(V*M + P - 1), the non-interleaved bubble shrunk by 1/V
  (see pipeline_forward_interleaved).

All schedule functions must run inside ``shard_map`` over ``axis_name``.
``stage_fn(params, x) -> y`` must be shape-uniform (y like x); embedding /
loss heads live outside the scan (pre_process/post_process in build_model).

Static validation: every edge these schedules ship is built from the
p2p edge grammar (p2p.forward_edges/backward_edges/ring_edges/
last_to_first_edges), and the trace-time collective-safety validator
(``apex_tpu.analysis.collectives``) checks traced schedules against it —
non-permutation edge sets and gapped chains (a stage whose input edge
fires while its feeder edge is missing: the static deadlock) are
findings. One honest caveat, as with the comms ledger: the BACKWARD
schedule's reversed edges are synthesized by jax's transpose rules and
never appear in a forward trace, so the validator sees them only when
the traced function includes ``jax.grad`` of the scan (the fwd+bwd
program), which all ``forward_backward_*`` entry points here do.

ZERO-BUBBLE (B/W split). ``forward_backward_zero_bubble`` (and its
pre/post twin) hand-write the backward pipeline instead of deriving it
from ``jax.grad``: the backward pass splits into B (activation-grad:
``dx``, the only value the reversed p2p chain carries) and W
(weight-grad: ``dp``, which feeds nothing but a local accumulator).
Expressing that split in the program's dataflow is what lets XLA's
latency-hiding scheduler fill each backward tick's edge-transfer wait
with W compute instead of idling — the compiled-scan realization of
zero-bubble scheduling (arXiv:2401.10241), whose predicted tick counts
and bubble fractions live in ``algebra.py`` and whose realized bubble
the timeline analyzer measures. Two structural consequences:

- the reversed edges are REAL ``p2p.send_backward_recv_backward`` calls,
  so the comms ledger predicts the backward pp traffic exactly (the
  transpose blind spot above closes for this schedule) and the HLO
  differ can match every emitted permute to a prediction;
- memory: the forward scan stashes its per-tick stage inputs AND outputs
  (2 boundary activations x T ticks — the deferred-W stash, vs the
  remat'd 1F1B's 1 x T carry residuals), and each backward tick
  recomputes the stage forward once inside its vjp, exactly the remat
  trade the fused path already pays.
"""

import functools
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp

from apex_tpu.monitor.xray import ledger as xlax
from apex_tpu.parallel.pipeline import p2p


def _leading_dim(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        raise ValueError("empty microbatch pytree")
    return leaves[0].shape[0]


def _index(tree: Any, i) -> Any:
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree
    )


def _varying_zeros(out_shape, axis_name: str):
    """Zero boundary-activation carry whose varying-manual-axes type is a
    FIXED POINT of the tick body: the stage output's vma (carried by the
    ``jax.eval_shape`` avals under checked shard_map — dp-varying data,
    tp-varying params, ...) plus ``axis_name`` (the in-scan ppermute makes
    the received edge pp-varying even when nothing else is). An unvarying
    zeros carry fails the scan typecheck the first time the body returns
    a varying value. ``pcast`` is a no-op under ``check_vma=False``."""

    from apex_tpu.parallel.utils import pcast_varying

    def one(s):
        z = jnp.zeros(s.shape, s.dtype)
        axes = set(getattr(s, "vma", ()) or ()) | {axis_name}
        return pcast_varying(z, tuple(sorted(axes)))

    return jax.tree_util.tree_map(one, out_shape)


def _scan_ticks(tick, state0, num_ticks: int, tick_block_remat: int):
    """Scan ``tick`` over ``num_ticks`` ticks, optionally rematerializing in
    blocks: with ``tick_block_remat = B > 0`` the scan nests — an outer scan
    over ceil(T/B) blocks whose body (an inner B-tick scan) is
    ``jax.checkpoint``ed, so differentiation stashes one carry per BLOCK
    instead of per tick: live boundary-activation memory drops from O(T) to
    O(T/B + B) at the cost of one forward recompute of each block — the
    knob that restores the reference 1F1B's O(P) in-flight bound
    (fwd_bwd_pipelining_without_interleaving.py:345-348) for large M.

    Returns (final_state, ys) like ``lax.scan``; padding ticks (to fill the
    last block) run the pipeline beyond its useful range, and callers index
    only real ticks out of ``ys``.
    """
    if tick_block_remat and 0 < tick_block_remat < num_ticks:
        # B >= T degenerates to one checkpointed block: every padding tick
        # runs a real ppermute + stage computation for zero residual
        # savings, so fall through to the plain scan instead
        B = tick_block_remat
        nblocks = -(-num_ticks // B)

        @jax.checkpoint
        def block(carry, tblock):
            return jax.lax.scan(tick, carry, tblock)

        ticks = jnp.arange(nblocks * B).reshape(nblocks, B)
        # the tick body traces ONCE but runs nblocks*B times (padding
        # ticks included — they ship real edges); the xray comms ledger
        # weighs its collectives accordingly
        with xlax.scaled(nblocks * B):
            state, ys = jax.lax.scan(block, state0, ticks)
        # un-block the stacked outputs: (nblocks, B, ...) -> (nblocks*B, ...)
        ys = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), ys
        )
        return state, ys
    with xlax.scaled(num_ticks):
        return jax.lax.scan(tick, state0, jnp.arange(num_ticks))


def pipeline_forward(
    stage_fn: Callable[[Any, Any], Any],
    params: Any,
    microbatches: Any,
    *,
    axis_name: str = "pp",
    remat: bool = True,
    tick_block_remat: int = 0,
) -> Any:
    """Run M microbatches through the P-stage compiled pipeline.

    ``microbatches``: pytree with leading dim M (stage-0 input; only the
    first stage reads it, so it may be garbage elsewhere). Returns a pytree
    with leading dim M of last-stage outputs — *valid on the last stage
    only* (other stages hold bubble garbage), mirroring how the reference's
    forward_step returns losses only on the final stage (common.py:296-309).

    Memory: the scan carry is ONE boundary activation; per-tick outputs are
    scan ys (microbatch m exits at the statically-known tick m + P - 1, so
    collecting them is a static slice, not a carried M-slot buffer — keeping
    the buffer in the carry would make every tick's residual O(M)).
    ``tick_block_remat`` bounds the per-tick residuals for large M
    (_scan_ticks).
    """
    num_stages = xlax.axis_size(axis_name)  # static inside shard_map
    rank = jax.lax.axis_index(axis_name)
    num_micro = _leading_dim(microbatches)
    body = jax.checkpoint(stage_fn) if remat else stage_fn

    mb0 = _index(microbatches, 0)
    with xlax.muted():  # shape probe, not part of the compiled program
        out_shape = jax.eval_shape(stage_fn, params, mb0)
    state0 = _varying_zeros(out_shape, axis_name)

    def tick(state, t):
        # named scopes are the per-phase timing taps: they label the HLO
        # ops, so profiler captures (monitor.ProfilerTrigger, utils.trace)
        # attribute each tick's time to edge-transfer vs stage compute
        with jax.named_scope("pp_p2p"):
            recv = p2p.send_forward_recv_forward(state, axis_name)
        mb = _index(microbatches, jnp.clip(t, 0, num_micro - 1))
        is_first = rank == 0
        x = jax.tree_util.tree_map(
            lambda a, b: jnp.where(is_first, a, b), mb, recv
        )
        with jax.named_scope("pp_stage"):
            y = body(params, x)
        return y, y

    num_ticks = num_micro + num_stages - 1
    _, ys = _scan_ticks(tick, state0, num_ticks, tick_block_remat)
    # microbatch m's last-stage output was produced at tick m + (P-1)
    return jax.tree_util.tree_map(
        lambda a: jax.lax.slice_in_dim(a, num_stages - 1, num_ticks, axis=0),
        ys,
    )


def pipeline_forward_interleaved(
    stage_fn: Callable[[Any, Any], Any],
    params_chunks: Any,
    microbatches: Any,
    *,
    num_model_chunks: int,
    axis_name: str = "pp",
    remat: bool = True,
    tick_block_remat: int = 0,
) -> Any:
    """Genuinely interleaved virtual-PP forward: ONE scan over
    T = V*M + P - 1 ticks, one chunk-computation per rank per tick.

    Chunk v on rank r implements global stage v*P + r (the reference's
    chunk-id map, fwd_bwd_pipelining_with_interleaving.py:221-259), and the
    per-rank work order is the reference's group-of-P depth-first pattern:
    microbatch group k = (kP..kP+P-1) runs chunks 0..V-1 before group k+1
    starts. Rank r processes, at tick t with u = t - r:
        k = u // (V*P), v = (u % (V*P)) // P, m = k*P + u % P.
    Each produced activation is consumed exactly one tick later by the next
    global stage — same-chunk hop (rank r+1) or the ring wrap (rank 0,
    chunk v+1) — so every tick ships ONE ring ppermute.

    Per-tick work is one chunk = 1/V of a rank's layers, and only P - 1 of
    the V*M + P - 1 ticks are bubble — bubble fraction (P-1)/(V*M + P - 1),
    i.e. the reference's ≈(P-1)/M shrunk by 1/V, unlike V sequential passes
    (V*(M + P - 1) ticks, bubble unchanged). Requires M % P == 0, as the
    reference asserts (:118).

    Returns last-stage outputs (leading dim M), valid on rank P-1 only.

    Memory: like ``pipeline_forward``, the carry is one boundary activation
    and outputs are scan ys gathered post-scan — on the last rank,
    microbatch m (group k = m // P, slot i = m % P) clears the final global
    stage at the statically-known tick k*V*P + (V-1)*P + i + (P-1), so the
    gather indices are a host-side constant.
    """
    num_stages = xlax.axis_size(axis_name)  # static inside shard_map
    rank = jax.lax.axis_index(axis_name)
    num_micro = _leading_dim(microbatches)
    V = num_model_chunks
    if num_micro % num_stages != 0:
        raise ValueError(
            f"interleaved schedule requires num_microbatches ({num_micro}) "
            f"% pipeline size ({num_stages}) == 0"
        )
    def chunk_fn(chunks, v, x):
        # the chunk gather lives INSIDE the rematerialized body: saved as a
        # residual it would cost one full chunk's params PER TICK;
        # rematerialized it costs nothing extra
        pv = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, v, 0, keepdims=False),
            chunks,
        )
        return stage_fn(pv, x)

    body = jax.checkpoint(chunk_fn) if remat else chunk_fn

    mb0 = _index(microbatches, 0)
    with xlax.muted():  # shape probe, not part of the compiled program
        out_shape = jax.eval_shape(body, params_chunks, 0, mb0)
    state0 = _varying_zeros(out_shape, axis_name)

    def tick(state, t):
        # per-phase profiler taps, as in pipeline_forward
        with jax.named_scope("pp_p2p"):
            recv = p2p.ring_forward(state, axis_name)
        u = t - rank
        uc = jnp.clip(u, 0, V * num_micro - 1)
        v = (uc % (V * num_stages)) // num_stages
        m = (uc // (V * num_stages)) * num_stages + uc % num_stages
        # fresh input only where the stream enters the model: rank 0, chunk 0
        takes_input = (rank == 0) & (v == 0)
        mb = _index(microbatches, m)
        x = jax.tree_util.tree_map(
            lambda a, b: jnp.where(takes_input, a, b), mb, recv
        )
        with jax.named_scope("pp_stage"):
            y = body(params_chunks, v, x)
        return y, y

    num_ticks = V * num_micro + num_stages - 1
    _, ys = _scan_ticks(tick, state0, num_ticks, tick_block_remat)
    # exit tick of microbatch m on the last rank (u = t - (P-1)):
    #   u_out = (m // P)*V*P + (V-1)*P + (m % P)
    ms = jnp.arange(num_micro)
    exit_ticks = (
        (ms // num_stages) * V * num_stages
        + (V - 1) * num_stages
        + ms % num_stages
        + num_stages
        - 1
    )
    return jax.tree_util.tree_map(lambda a: a[exit_ticks], ys)


def _stages_forward(
    stage_fn, stages_params, h, *, axis_name: str, remat: bool,
    num_model_chunks: int, tick_block_remat: int = 0,
):
    """Forward through this rank's chunk(s): the plain pipeline for V=1,
    the single-scan interleaved schedule for V>1."""
    if num_model_chunks == 1:
        return pipeline_forward(
            stage_fn, stages_params, h, axis_name=axis_name, remat=remat,
            tick_block_remat=tick_block_remat,
        )
    return pipeline_forward_interleaved(
        stage_fn, stages_params, h, num_model_chunks=num_model_chunks,
        axis_name=axis_name, remat=remat, tick_block_remat=tick_block_remat,
    )


def _publish_losses(per_microbatch_losses, axis_name: str):
    """Mask bubble garbage off non-final stages, publish the mean loss and
    the per-microbatch losses from the last stage to every stage."""
    num_stages = xlax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    losses = jnp.where(rank == num_stages - 1, per_microbatch_losses, 0.0)
    loss = _last_stage_mean_loss(losses, axis_name)
    return loss, xlax.psum(losses, axis_name)


def _last_stage_mean_loss(per_microbatch_losses, axis_name: str):
    """Average per-microbatch losses and publish from the last stage to all
    stages (ref: losses divided by num_microbatches on the last stage,
    common.py:305-309; other stages return nothing)."""
    num_stages = xlax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    mean = jnp.mean(per_microbatch_losses)
    local = jnp.where(rank == num_stages - 1, mean, 0.0)
    # Publish the value via psum but keep only the LOCAL term on the grad
    # path: psum's transpose would re-sum the replicated cotangent and
    # scale grads by P. With the local term, the loss cotangent enters the
    # graph once (on the last stage) and the ppermute transposes carry it
    # back through every stage exactly as the reference's backward phases.
    return local + jax.lax.stop_gradient(
        xlax.psum(local, axis_name) - local
    )


# -- zero-bubble (B/W split) -------------------------------------------------


def _zb_forward_scan(
    stage_fn, params, microbatches, *, axis_name: str, remat: bool,
    tick_block_remat: int,
):
    """The zero-bubble forward pass: ``pipeline_forward``'s tick loop,
    additionally stashing every tick's stage INPUT (the value the
    backward scan's per-tick vjp replays — the deferred-W stash).

    Returns ``(xs, outs)``: ``xs`` with leading dim T = M + P - 1 (this
    stage's input at each tick, bubble ticks included), ``outs`` with
    leading dim M (last-stage outputs, valid on the last stage only).
    """
    num_stages = xlax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    num_micro = _leading_dim(microbatches)
    body = jax.checkpoint(stage_fn) if remat else stage_fn

    mb0 = _index(microbatches, 0)
    with xlax.muted():  # shape probe, not part of the compiled program
        out_shape = jax.eval_shape(stage_fn, params, mb0)
    state0 = _varying_zeros(out_shape, axis_name)

    def tick(state, t):
        with jax.named_scope("pp_p2p"):
            recv = p2p.send_forward_recv_forward(state, axis_name)
        mb = _index(microbatches, jnp.clip(t, 0, num_micro - 1))
        is_first = rank == 0
        x = jax.tree_util.tree_map(
            lambda a, b: jnp.where(is_first, a, b), mb, recv
        )
        with jax.named_scope("pp_stage"):
            y = body(params, x)
        return y, (x, y)

    num_ticks = num_micro + num_stages - 1
    _, (xs, ys) = _scan_ticks(tick, state0, num_ticks, tick_block_remat)
    outs = jax.tree_util.tree_map(
        lambda a: jax.lax.slice_in_dim(a, num_stages - 1, num_ticks, axis=0),
        ys,
    )
    return xs, outs


def _zb_backward_scan(stage_fn, params, xs, seed, *, axis_name: str,
                      num_micro: int):
    """The hand-written backward pipeline: a reverse-clock scan of
    T = M + P - 1 ticks whose tick body splits B from W.

    At reverse tick q every stage replays its forward tick t = T - 1 - q
    (the backward schedule is the forward's exact mirror: stage s handled
    microbatch m = t - s there, so the reversal needs no per-stage index
    algebra — only the shared clock flips). The tick:

    - receives the downstream cotangent over a REAL backward edge
      (``send_backward_recv_backward`` — ledger-recorded, unlike the
      transpose-synthesized edges of the ``jax.grad`` path);
    - the last stage swaps in its own loss seed for the microbatch that
      exited at t;
    - one ``jax.vjp`` replay of the stage yields both halves, but only
      ``dx`` (B) enters the carried edge chain — ``dp`` (W) feeds the
      grad accumulator, a dataflow XLA's latency-hiding scheduler is
      free to move into the edge-transfer wait (the zero-bubble filling;
      ``algebra.zero_bubble_cost`` is its tick-count model);
    - bubble ticks (this stage outside its valid window) contribute
      exact zeros to both halves.

    Returns ``(stage_grads, dxs)`` where ``dxs`` (leading dim T) holds
    each tick's masked ``dx`` — stage 0's entries are the cotangents of
    its microbatch inputs, which the pre/post variant feeds to the
    embedding vjp.
    """
    num_stages = xlax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    num_ticks = num_micro + num_stages - 1

    x0 = _index(xs, 0)
    with xlax.muted():  # shape probes only
        out_shape = jax.eval_shape(stage_fn, params, x0)
        p_shape = jax.eval_shape(lambda p: p, params)
    d0 = _varying_zeros(out_shape, axis_name)
    g0 = _varying_zeros(p_shape, axis_name)

    def btick(carry, q):
        dprev, gacc = carry
        with jax.named_scope("pp_p2p_bwd"):
            recv = p2p.send_backward_recv_backward(dprev, axis_name)
        t = num_ticks - 1 - q
        x = _index(xs, t)
        # the microbatch exiting the LAST stage at forward tick t seeds
        # its loss cotangent here; everyone else consumes the edge
        m = t - (num_stages - 1)
        seed_m = _index(seed, jnp.clip(m, 0, num_micro - 1))
        is_seed = (rank == num_stages - 1) & (m >= 0) & (m < num_micro)
        dy = jax.tree_util.tree_map(
            lambda s, r: jnp.where(is_seed, s, r), seed_m, recv
        )
        # this stage's valid window mirrors the forward's: u = t - rank
        u = t - rank
        valid = (u >= 0) & (u < num_micro)
        with jax.named_scope("pp_stage_bwd"):
            _, pull = jax.vjp(stage_fn, params, x)
            dp, dx = pull(dy)
        dx = jax.tree_util.tree_map(
            lambda a: jnp.where(valid, a, jnp.zeros_like(a)), dx
        )
        dp = jax.tree_util.tree_map(
            lambda a: jnp.where(valid, a, jnp.zeros_like(a)), dp
        )
        gacc = jax.tree_util.tree_map(jnp.add, gacc, dp)
        return (dx, gacc), dx

    with xlax.scaled(num_ticks):
        (_, grads), dxs = jax.lax.scan(
            btick, (d0, g0), jnp.arange(num_ticks)
        )
    return grads, dxs


def _loss_seed_cotangent(num_micro: int, axis_name: str):
    """d(published mean loss)/d(per-microbatch losses): 1/M on the last
    stage (only its losses reach the mean — ``_last_stage_mean_loss``
    keeps just the local term on the grad path), zero elsewhere."""
    num_stages = xlax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    return jnp.where(
        rank == num_stages - 1,
        jnp.full((num_micro,), 1.0 / num_micro),
        jnp.zeros((num_micro,)),
    )


def forward_backward_zero_bubble(
    stage_fn: Callable[[Any, Any], Any],
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    params: Any,
    microbatches: Any,
    targets: Any,
    *,
    axis_name: str = "pp",
    remat: bool = True,
    tick_block_remat: int = 0,
    grad_sync_fn: Optional[Callable[[Any], Any]] = None,
):
    """Zero-bubble-style schedule: same signature and same gradients as
    ``forward_backward_pipelining_without_interleaving``, backward
    hand-written with the B/W split (module docstring). Tick counts and
    the predicted bubble fraction: ``algebra.zero_bubble_cost(P, M)``.
    """
    num_micro = _leading_dim(microbatches)
    xs, outs = _zb_forward_scan(
        stage_fn, params, microbatches, axis_name=axis_name, remat=remat,
        tick_block_remat=tick_block_remat,
    )
    losses, loss_pull = jax.vjp(
        lambda o: jax.vmap(loss_fn)(o, targets), outs
    )
    loss, losses_pub = _publish_losses(losses, axis_name)
    (douts,) = loss_pull(_loss_seed_cotangent(num_micro, axis_name))
    grads, _ = _zb_backward_scan(
        stage_fn, params, xs, douts, axis_name=axis_name,
        num_micro=num_micro,
    )
    if grad_sync_fn is not None:
        grads = grad_sync_fn(grads)
    return loss, losses_pub, grads


def forward_backward_zero_bubble_with_pre_post(
    pre_fn: Callable[[Any, Any], Any],
    stage_fn: Callable[[Any, Any], Any],
    post_loss_fn: Callable[[Any, Any, Any], jnp.ndarray],
    params: Any,
    inputs: Any,
    targets: Any,
    *,
    axis_name: str = "pp",
    remat: bool = True,
    tick_block_remat: int = 0,
    grad_sync_fn: Optional[Callable[[Any], Any]] = None,
):
    """``forward_backward_with_pre_post`` with the zero-bubble backward:
    embedding + stages + head in one B/W-split program, gradients equal
    to the fused path's.

    The pre/post halves ride the stage machinery: the head's loss vjp
    provides the last-stage seeds, and stage 0's per-tick ``dx`` stash
    IS the embedding-output cotangent (microbatch m's entry lands at
    reverse tick (M-1-m) + (P-1), a host-side constant), so the
    embedding vjp needs no extra pipeline pass. Replicated pre/post
    grads are combined over pp exactly as in the fused variant.
    """
    num_stages = xlax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    num_micro = _leading_dim(inputs)

    def pre_all(pre):
        with jax.named_scope("pp_pre"):
            return jax.vmap(lambda mb: pre_fn(pre, mb))(inputs)

    h, pre_pull = jax.vjp(pre_all, params["pre"])
    xs, outs = _zb_forward_scan(
        stage_fn, params["stages"], h, axis_name=axis_name, remat=remat,
        tick_block_remat=tick_block_remat,
    )

    def post_all(post, o):
        with jax.named_scope("pp_post"):
            return jax.vmap(
                lambda y, t: post_loss_fn(post, y, t)
            )(o, targets)

    losses, post_pull = jax.vjp(post_all, params["post"], outs)
    loss, losses_pub = _publish_losses(losses, axis_name)
    dpost, douts = post_pull(_loss_seed_cotangent(num_micro, axis_name))
    stage_grads, dxs = _zb_backward_scan(
        stage_fn, params["stages"], xs, douts, axis_name=axis_name,
        num_micro=num_micro,
    )
    # microbatch m entered stage 0 at forward tick m, i.e. reverse tick
    # (T-1) - m = (M-1-m) + (P-1) — static gather indices for dL/dh
    qs = (num_micro - 1 - jnp.arange(num_micro)) + (num_stages - 1)
    dh = jax.tree_util.tree_map(lambda a: a[qs], dxs)
    # only stage 0 consumed h; its dx rows are the real cotangents
    dh = jax.tree_util.tree_map(
        lambda a: jnp.where(rank == 0, a, jnp.zeros_like(a)), dh
    )
    (dpre,) = pre_pull(dh)

    grads = {
        "pre": _combine_replicated_grads(dpre, axis_name),
        "stages": stage_grads,
        "post": _combine_replicated_grads(dpost, axis_name),
    }
    if grad_sync_fn is not None:
        grads = grad_sync_fn(grads)
    return loss, losses_pub, grads


def _combine_replicated_grads(tree, axis_name: str):
    """Combine pp-replicated params' grads (nonzero on one rank only)
    onto every rank — the tied-embedding allreduce semantics, with the
    checked-shard_map dispatch of ``forward_backward_with_pre_post``:
    under live vma tracking the transpose already psummed replicated
    leaves, and a second psum would multiply by P."""
    from apex_tpu.parallel.ddp import grads_already_reduced, vma_tracking_live

    tracking = vma_tracking_live(axis_name)

    def one(g):
        if grads_already_reduced(g, axis_name, tracking):
            return g
        return xlax.psum(g, axis_name)

    return jax.tree_util.tree_map(one, tree)


def forward_backward_no_pipelining(
    forward_step_fn: Callable[[Any, Any], jnp.ndarray],
    params: Any,
    microbatches: Any,
    *,
    grad_sync_fn: Optional[Callable[[Any], Any]] = None,
):
    """Gradient accumulation over microbatches, no pipeline (ref:
    fwd_bwd_no_pipelining.py:23).

    ``forward_step_fn(params, microbatch) -> scalar loss``. Gradients are
    accumulated across all microbatches and synchronized ONCE at the end via
    ``grad_sync_fn`` (e.g. a dp psum) — the reference's "no_sync on all but
    the last microbatch" semantics (:37-48). Returns
    ``(mean_loss, per_microbatch_losses, grads)``.
    """
    num_micro = _leading_dim(microbatches)
    grad_fn = jax.value_and_grad(forward_step_fn)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

    def body(acc, mb):
        loss, g = grad_fn(params, mb)
        acc = jax.tree_util.tree_map(jnp.add, acc, g)
        return acc, loss

    grads, losses = jax.lax.scan(body, zeros, microbatches)
    grads = jax.tree_util.tree_map(lambda g: g / num_micro, grads)
    if grad_sync_fn is not None:
        grads = grad_sync_fn(grads)
    return jnp.mean(losses), losses, grads


def forward_backward_pipelining_without_interleaving(
    stage_fn: Callable[[Any, Any], Any],
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    params: Any,
    microbatches: Any,
    targets: Any,
    *,
    axis_name: str = "pp",
    remat: bool = True,
    tick_block_remat: int = 0,
    grad_sync_fn: Optional[Callable[[Any], Any]] = None,
):
    """Compiled 1F1B-equivalent schedule (ref:
    fwd_bwd_pipelining_without_interleaving.py:241).

    ``loss_fn(last_stage_output, target) -> scalar`` is applied per
    microbatch on the last stage; the mean loss is psum-published so every
    stage returns the same scalar. Returns
    ``(loss, per_microbatch_losses, grads)`` where ``grads`` matches this
    stage's ``params`` — the backward pipeline (warmup/steady/cooldown of
    the reference) emerges from differentiating the forward scan.
    """
    def total_loss(p):
        outs = pipeline_forward(
            stage_fn, p, microbatches, axis_name=axis_name, remat=remat,
            tick_block_remat=tick_block_remat,
        )
        return _publish_losses(jax.vmap(loss_fn)(outs, targets), axis_name)

    (loss, losses), grads = jax.value_and_grad(total_loss, has_aux=True)(params)
    if grad_sync_fn is not None:
        grads = grad_sync_fn(grads)
    return loss, losses, grads


def forward_backward_pipelining_with_interleaving(
    stage_fn: Callable[[Any, Any], Any],
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    params_chunks: Any,
    microbatches: Any,
    targets: Any,
    *,
    num_model_chunks: int,
    axis_name: str = "pp",
    remat: bool = True,
    tick_block_remat: int = 0,
    grad_sync_fn: Optional[Callable[[Any], Any]] = None,
):
    """Virtual-pipeline (interleaved) schedule (ref:
    fwd_bwd_pipelining_with_interleaving.py:27).

    ``params_chunks`` carries a leading dim V = num_model_chunks on every
    leaf: this stage's V model chunks, where chunk v on rank r implements
    global stage v*P + r — the reference's chunk-id mapping (:221-259). The
    microbatch stream makes V circular passes over the P ranks, chained by
    a last→first ring edge, so the layer order is exactly the reference's
    interleaved assignment.
    """
    def total_loss(chunks):
        outs = _stages_forward(
            stage_fn, chunks, microbatches, axis_name=axis_name,
            remat=remat, num_model_chunks=num_model_chunks,
            tick_block_remat=tick_block_remat,
        )
        return _publish_losses(jax.vmap(loss_fn)(outs, targets), axis_name)

    (loss, losses), grads = jax.value_and_grad(total_loss, has_aux=True)(
        params_chunks
    )
    if grad_sync_fn is not None:
        grads = grad_sync_fn(grads)
    return loss, losses, grads


def forward_backward_with_pre_post(
    pre_fn: Callable[[Any, Any], Any],
    stage_fn: Callable[[Any, Any], Any],
    post_loss_fn: Callable[[Any, Any, Any], jnp.ndarray],
    params: Any,
    inputs: Any,
    targets: Any,
    *,
    axis_name: str = "pp",
    remat: bool = True,
    num_model_chunks: int = 1,
    tick_block_remat: int = 0,
    grad_sync_fn: Optional[Callable[[Any], Any]] = None,
):
    """Full-model pipeline step: embedding + stages + head in one backward.

    ``params`` is a dict ``{"pre": …, "stages": …, "post": …}``:
    - ``pre`` (e.g. the embedding) and ``post`` (final norm + head/loss)
      are REPLICATED across pp ranks; only stage 0 / the last stage's
      compute reaches the loss, so their raw grads are nonzero on one rank
      only — they are psum-synced over pp afterwards, which is exactly the
      reference's first/last-stage embedding-group grad allreduce for tied
      embeddings (parallel_state.py:319-407 embedding groups);
    - ``stages`` holds this rank's chunk params (leading dim V when
      ``num_model_chunks`` > 1, chunk v = global stage v*P + rank).

    ``pre_fn(pre_params, input_mb) -> h``; ``stage_fn(chunk_params, h) ->
    h``; ``post_loss_fn(post_params, h, target_mb) -> scalar``. Returns
    ``(loss, per_microbatch_losses, grads)`` with grads matching
    ``params``.
    """
    def total_loss(p):
        # pre/stages/post named scopes: the per-phase breakdown a profiler
        # capture shows for the full pipelined step
        with jax.named_scope("pp_pre"):
            h = jax.vmap(lambda mb: pre_fn(p["pre"], mb))(inputs)
        outs = _stages_forward(
            stage_fn, p["stages"], h, axis_name=axis_name, remat=remat,
            num_model_chunks=num_model_chunks,
            tick_block_remat=tick_block_remat,
        )
        with jax.named_scope("pp_post"):
            losses = jax.vmap(
                lambda y, t: post_loss_fn(p["post"], y, t)
            )(outs, targets)
        return _publish_losses(losses, axis_name)

    (loss, losses), grads = jax.value_and_grad(total_loss, has_aux=True)(params)
    # replicated pre/post params: combine the single contributing rank's
    # grads onto every rank (tied-embedding allreduce semantics) — the
    # shared vma-dispatched helper the zero-bubble variant also uses
    grads = dict(grads)
    grads["pre"] = _combine_replicated_grads(grads["pre"], axis_name)
    grads["post"] = _combine_replicated_grads(grads["post"], axis_name)
    if grad_sync_fn is not None:
        grads = grad_sync_fn(grads)
    return loss, losses, grads


def get_forward_backward_func(
    virtual_pipeline_model_parallel_size: Optional[int],
    pipeline_model_parallel_size: int,
    zero_bubble: bool = False,
) -> Callable:
    """Schedule dispatcher (ref: schedules/__init__.py:22): interleaved iff
    virtual PP is set, 1F1B iff PP > 1, else plain grad accumulation.
    ``zero_bubble=True`` swaps the 1F1B schedule for the B/W-split
    ``forward_backward_zero_bubble`` (same signature, same gradients;
    predicted bubble per ``algebra.zero_bubble_cost``). Virtual PP has
    no zero-bubble variant yet — the combination raises."""
    if virtual_pipeline_model_parallel_size is not None:
        if pipeline_model_parallel_size <= 1:
            raise ValueError(
                "virtual pipeline parallelism requires pipeline_model_parallel_size > 1"
            )
        if zero_bubble:
            raise ValueError(
                "zero_bubble has no interleaved variant: pick virtual PP "
                "(bubble/V) or the B/W split, not both"
            )
        return functools.partial(
            forward_backward_pipelining_with_interleaving,
            num_model_chunks=virtual_pipeline_model_parallel_size,
        )
    if pipeline_model_parallel_size > 1:
        if zero_bubble:
            return forward_backward_zero_bubble
        return forward_backward_pipelining_without_interleaving
    return forward_backward_no_pipelining


def build_model(
    model_provider_func: Callable[..., Any],
    pipeline_rank: int,
    pipeline_world_size: int,
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    **kwargs,
) -> List[Any]:
    """Construct this pipeline stage's model chunk(s) with pre/post flags
    (ref: schedules/common.py:30-108).

    ``model_provider_func(pre_process=..., post_process=..., **kwargs)``
    builds one chunk; ``pre_process`` is True only for global stage 0
    (owns the embedding), ``post_process`` only for the final global stage
    (owns the head/loss) — the reference's flags at common.py:83-108. With
    virtual PP, chunk v on rank r is global stage v*P + r, so rank 0 chunk 0
    gets pre_process and rank P-1 chunk V-1 gets post_process.

    Host-side helper: in SPMD there is no per-process rank, so the caller
    names the stage being built (e.g. when stacking per-stage params for a
    'pp'-sharded leading axis).
    """
    v = virtual_pipeline_model_parallel_size or 1
    chunks = []
    for chunk_id in range(v):
        global_stage = chunk_id * pipeline_world_size + pipeline_rank
        pre = global_stage == 0
        post = global_stage == v * pipeline_world_size - 1
        chunks.append(
            model_provider_func(pre_process=pre, post_process=post, **kwargs)
        )
    return chunks
