"""Context parallelism: ring attention + Ulysses all-to-all attention.

The reference has NO context/ring parallelism (SURVEY.md §2.5: its
long-context story tops out at Megatron sequence parallelism plus a
seq<=512 fused MHA kernel, contrib/fmha). This module is the long-context
subsystem the build brief makes first-class: sequence-sharded exact
attention over the 'cp' mesh axis, scaling max context length linearly in
the number of chips.

Two strategies, both exact:

- **Ring attention** (`ring_attention`): every rank keeps its query chunk;
  K/V chunks rotate around the cp ring via ``ppermute`` while an online
  (flash-style) softmax accumulates in fp32. Each ring step processes the
  visiting K/V chunk in ``block_size`` slices through an inner ``lax.scan``
  with the same online-softmax update, so local memory is
  O(s_local x block_size) — never the full (s_local, s_local) score matrix.
  The backward is NOT autodiff through the forward scan (which would stash
  every rotated K/V — O(cp) memory): a ``custom_vjp`` runs a second ring
  pass that recomputes probabilities blockwise from the saved logsumexp and
  rotates dK/dV accumulators *with* their chunks. The first ring step uses
  the resident chunk, so each pass issues exactly P-1 forward rotations
  (plus one homing rotation in backward), and XLA's latency-hiding
  scheduler overlaps each step's ppermute with the next step's matmuls.
- **Ulysses** (`ulysses_attention`): two ``all_to_all``s repartition
  sequence-sharded activations to head-sharded, run the full-sequence
  Pallas flash kernel locally, and repartition back. Cheaper collectives
  for moderate contexts; requires heads % cp == 0 (and kv_heads % cp == 0
  under GQA).

Both strategies take GQA/MQA-grouped K/V (heads % kv_heads == 0; the ring
rotates the grouped heads — heads/kv_heads x less ICI traffic than
repeating before the ring) and a sequence-sharded ``key_padding_mask``
whose local shard rotates/gathers with its keys; an all-padded visiting
chunk is skipped like an out-of-band one.

Causal handling in the ring: masks and chunk skipping are driven by GLOBAL
position vectors (``_positions``/``_band_keep``), so chunk layout is a
parameter. Contiguous layout keeps the classic behavior — chunk j vs local
queries of rank i: (j < i) full, (j == i) causal, (j > i) skipped entirely
(``_chunk_contributes`` + ``lax.cond``; sliding windows additionally skip
chunks behind the band) — but late ranks do more work per lockstep
rotation. ``zigzag=True`` (with ``zigzag_shard``-prepared inputs) gives
every rank one early and one late sequence piece, equalizing per-rotation
causal work across ranks.
"""

import functools
import math

import jax
import jax.numpy as jnp

from apex_tpu.monitor.xray import ledger as xlax

_NEG_INF = -1e30


def _rotate(tree, axis_name: str):
    """Move every leaf one rank down the ring (rank r -> r+1 mod P)."""
    n = xlax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return jax.tree_util.tree_map(
        lambda x: xlax.ppermute(x, axis_name, perm), tree
    )


def _positions(src, num_ranks, s_local: int, zigzag: bool):
    """(s_local,) GLOBAL sequence positions of rank ``src``'s chunk
    (``src`` may be traced).

    - contiguous (zigzag=False): rank r holds rows [r*s, (r+1)*s).
    - zigzag: the sequence is cut into 2P pieces and rank r holds pieces
      (r, 2P-1-r) concatenated — the causal-ring load balance: every rank
      owns one early and one late piece, so per-rotation work is equal
      instead of growing with rank index."""
    if not zigzag:
        return src * s_local + jnp.arange(s_local, dtype=jnp.int32)
    half = s_local // 2
    base = jnp.arange(half, dtype=jnp.int32)
    return jnp.concatenate([
        src * half + base,
        (2 * num_ranks - 1 - src) * half + base,
    ])


def _band_keep(rows, cols, causal: bool, window=None):
    """Keep-mask (len(rows), len(cols)) from GLOBAL positions, or None when
    nothing is masked. One band definition for both chunk layouts."""
    if not causal and window is None:
        return None
    r = rows[:, None]
    c = cols[None, :]
    keep = jnp.bool_(True)
    if causal:
        keep = jnp.logical_and(keep, c <= r)
    if window is not None:
        keep = jnp.logical_and(keep, c > r - window)
    return keep


def _chunk_contributes(rows, cols, causal: bool, window, pieces: int = 1):
    """Whether the visiting chunk's band intersects the local queries —
    the chunk is SKIPPED entirely (lax.cond) otherwise, making a windowed
    ring cost O(window + sq) keys per rank instead of O(seq).

    ``pieces`` is the number of CONTIGUOUS position runs per chunk (1
    contiguous, 2 zigzag). Bounds are evaluated per piece pair — a single
    min/max over a split zigzag chunk would span nearly the whole
    sequence and never skip anything, losing the windowed ring's
    O(window) scaling. Within a piece positions ascend, so min/max are
    its end elements."""
    if window is None and not causal:
        return jnp.bool_(True)
    r = rows.reshape(pieces, -1)
    c = cols.reshape(pieces, -1)
    rmin, rmax = r[:, 0], r[:, -1]
    cmin, cmax = c[:, 0], c[:, -1]
    pair_ok = jnp.ones((pieces, pieces), bool)
    if causal:
        pair_ok = jnp.logical_and(pair_ok, cmin[None, :] <= rmax[:, None])
    if window is not None:
        pair_ok = jnp.logical_and(
            pair_ok, cmax[None, :] > rmin[:, None] - window
        )
    return jnp.any(pair_ok)


def _chunk_block_size(s_local: int, block_size: int) -> int:
    bk = min(block_size, s_local)
    while s_local % bk != 0:  # s_local is a power-of-two-ish shard; cheap
        bk -= 1
    return bk


def _allow_mask(rows, cols_b, causal, window, keep_b):
    """Combined (sq, bk) band mask x (b, bk) key-validity mask, broadcast
    to the grouped score shape (b, G, g, sq, bk); None when unmasked."""
    band = _band_keep(rows, cols_b, causal, window)
    allow = None
    if band is not None:
        allow = band[None, None, None]
    if keep_b is not None:
        kb = keep_b[:, None, None, None, :]
        allow = kb if allow is None else jnp.logical_and(allow, kb)
    return allow


def _online_chunk_update(state, q, kc, vc, scale, rows, cols, causal,
                         block_size, window=None, keep=None):
    """Stream one visiting K/V chunk through the online softmax in
    ``block_size`` slices. state = (acc, m, l) accumulated so far;
    ``rows``/``cols`` are the global positions of the local queries and
    the visiting keys (any layout).

    ``q`` is GQA-grouped (b, h_kv, g, sq, d) against kc/vc (b, h_kv, s, d)
    — grouped K/V means the ring rotates h_kv heads, not h (g x less ICI
    traffic than repeating K/V before the ring).  ``keep`` is the visiting
    chunk's (b, s_kv) key-validity mask (False = padded-out key).

    Dot operands KEEP the input dtype (bf16 stays bf16) with fp32
    accumulation — upcasting before the einsum forces the MXU's slow fp32
    path (same policy as ops/attention.py); softmax math stays fp32."""
    s_kv = kc.shape[-2]
    bk = _chunk_block_size(s_kv, block_size)
    num_blocks = s_kv // bk
    from apex_tpu.parallel.utils import promote_to_vma

    state = promote_to_vma(state, rows)

    def block_step(carry, j):
        acc, m, l = carry
        lo = j * bk
        kb = jax.lax.dynamic_slice_in_dim(kc, lo, bk, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vc, lo, bk, axis=2)
        s = (
            jnp.einsum("bGgqd,bGkd->bGgqk", q, kb,
                       preferred_element_type=jnp.float32)
            * scale
        )
        allow = _allow_mask(
            rows, jax.lax.dynamic_slice_in_dim(cols, lo, bk, axis=0),
            causal, window,
            None if keep is None
            else jax.lax.dynamic_slice_in_dim(keep, lo, bk, axis=1),
        )
        if allow is not None:
            s = jnp.where(allow, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if allow is not None:
            p = jnp.where(allow, p, 0.0)  # exp(-inf - (-inf)) guard
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bGgqk,bGkd->bGgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, l_new), None

    if num_blocks == 1:
        state, _ = block_step(state, jnp.int32(0))
        return state
    state, _ = jax.lax.scan(block_step, state, jnp.arange(num_blocks))
    return state


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring(q, k, v, kbias, axis_name, causal, scale, block_size, window, zigzag):
    o, _ = _ring_fwd_res(
        q, k, v, kbias, axis_name, causal, scale, block_size, window, zigzag
    )
    return o


def _bias_placeholder(b: int, axis_name: str):
    """Rotatable stand-in for a None key-padding bias in the ring scan
    carry — typed varying so it survives the in-scan ppermute's vma under
    checked shard_map (identity under check_vma=False)."""
    from apex_tpu.parallel.utils import pcast_varying

    return pcast_varying(jnp.zeros((b, 0)), axis_name)


def _keep_from_bias(kbias):
    """(b, s) float bias (0 valid / _NEG_INF padded) -> bool validity mask.
    The bias is float (not bool) only so it can ride the custom_vjp as a
    differentiable primal with a zero cotangent."""
    return None if kbias is None else kbias > 0.5 * _NEG_INF


def _ring_fwd_res(q, k, v, kbias, axis_name, causal, scale, block_size,
                  window, zigzag):
    num_ranks = xlax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    h_kv = k.shape[1]
    g = h // h_kv
    q5 = q.reshape(b, h_kv, g, sq, d)
    rows = _positions(rank, num_ranks, sq, zigzag)
    keep0 = _keep_from_bias(kbias)

    init_state = (
        jnp.zeros((b, h_kv, g, sq, d), jnp.float32),
        jnp.full((b, h_kv, g, sq), _NEG_INF, jnp.float32),
        jnp.zeros((b, h_kv, g, sq), jnp.float32),
    )
    # step 0 on the resident chunk — no rotation needed
    state = _online_chunk_update(
        init_state, q5, k, v, scale, rows, rows, causal, block_size, window,
        keep0,
    )

    def step(carry, t):
        (kc, vc, biasc), state = carry
        kc, vc, biasc = _rotate((kc, vc, biasc), axis_name)
        src = jax.lax.rem(rank - t + num_ranks, num_ranks)
        cols = _positions(src, num_ranks, sq, zigzag)
        # trace-time None check: with no kpm the carry holds a (b, 0)
        # placeholder, which must NOT become an all-False keep mask
        keep_c = _keep_from_bias(biasc) if kbias is not None else None
        contributes = _chunk_contributes(rows, cols, causal, window,
                                         2 if zigzag else 1)
        if keep_c is not None:
            # an all-padded visiting chunk is skipped like an out-of-band one
            contributes = jnp.logical_and(contributes, jnp.any(keep_c))
        state = jax.lax.cond(
            contributes,
            lambda st: _online_chunk_update(
                st, q5, kc, vc, scale, rows, cols, causal, block_size,
                window, keep_c,
            ),
            lambda st: st,
            state,
        )
        return ((kc, vc, biasc), state), None

    if num_ranks > 1:
        bias_carry = (kbias if kbias is not None
                      else _bias_placeholder(b, axis_name))
        # in-scan ppermutes make every carried leaf axis-varying; promote
        # the initial carry so its type is already the fixed point even
        # when the caller's q/k/v arrive axis-replicated (per-leaf no-op
        # when already varying / under check_vma=False)
        from apex_tpu.parallel.utils import pvary_params

        carry0 = pvary_params(((k, v, bias_carry), state), axis_name)
        # the rotation traces once but runs P-1 times (comms accounting)
        with xlax.scaled(num_ranks - 1):
            ((_, _, _), state), _ = jax.lax.scan(
                step, carry0, jnp.arange(1, num_ranks)
            )
    acc, m, l = state
    l = jnp.maximum(l, 1e-30)
    o = (acc / l[..., None]).reshape(b, h, sq, d).astype(q.dtype)
    lse = m + jnp.log(l)  # (b, h_kv, g, sq)
    return o, (q, k, v, kbias, o, lse)


def _chunk_bwd_update(q, do, delta, lse, kc, vc, dkc, dvc, dq, scale, rows,
                      cols, causal, block_size, window=None, keep=None):
    """Blockwise gradient contributions of one visiting K/V chunk.
    GQA-grouped like _online_chunk_update (q/do/delta/lse carry the
    (b, h_kv, g, ...) layout; kc/vc/dkc/dvc the (b, h_kv, ...) one).
    Operand-dtype policy as in _online_chunk_update; dkc/dvc/dq accumulate
    in fp32."""
    s_kv = kc.shape[-2]
    bk = _chunk_block_size(s_kv, block_size)
    num_blocks = s_kv // bk
    from apex_tpu.parallel.utils import promote_to_vma

    dkc, dvc, dq = promote_to_vma((dkc, dvc, dq), rows)

    def block_step(carry, j):
        dkc, dvc, dq = carry
        lo = j * bk
        kb = jax.lax.dynamic_slice_in_dim(kc, lo, bk, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vc, lo, bk, axis=2)
        s = (
            jnp.einsum("bGgqd,bGkd->bGgqk", q, kb,
                       preferred_element_type=jnp.float32)
            * scale
        )
        allow = _allow_mask(
            rows, jax.lax.dynamic_slice_in_dim(cols, lo, bk, axis=0),
            causal, window,
            None if keep is None
            else jax.lax.dynamic_slice_in_dim(keep, lo, bk, axis=1),
        )
        if allow is not None:
            s = jnp.where(allow, s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])
        if allow is not None:
            p = jnp.where(allow, p, 0.0)
        dv_b = jnp.einsum(
            "bGgqk,bGgqd->bGkd", p.astype(do.dtype), do,
            preferred_element_type=jnp.float32,
        )
        dp = jnp.einsum(
            "bGgqd,bGkd->bGgqk", do, vb, preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[..., None]) * scale
        ds_lo = ds.astype(kb.dtype)
        dq = dq + jnp.einsum(
            "bGgqk,bGkd->bGgqd", ds_lo, kb, preferred_element_type=jnp.float32
        )
        dk_b = jnp.einsum(
            "bGgqk,bGgqd->bGkd", ds_lo, q, preferred_element_type=jnp.float32
        )
        dkc = jax.lax.dynamic_update_slice_in_dim(
            dkc, jax.lax.dynamic_slice_in_dim(dkc, lo, bk, 2) + dk_b, lo, 2
        )
        dvc = jax.lax.dynamic_update_slice_in_dim(
            dvc, jax.lax.dynamic_slice_in_dim(dvc, lo, bk, 2) + dv_b, lo, 2
        )
        return (dkc, dvc, dq), None

    if num_blocks == 1:
        (dkc, dvc, dq), _ = block_step((dkc, dvc, dq), jnp.int32(0))
    else:
        (dkc, dvc, dq), _ = jax.lax.scan(
            block_step, (dkc, dvc, dq), jnp.arange(num_blocks)
        )
    return dkc, dvc, dq


def _ring_bwd(axis_name, causal, scale, block_size, window, zigzag, res, do):
    q, k, v, kbias, o, lse = res
    num_ranks = xlax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    h_kv = k.shape[1]
    g = h // h_kv
    q5 = q.reshape(b, h_kv, g, sq, d)
    do5 = do.reshape(b, h_kv, g, sq, d)
    o5 = o.reshape(b, h_kv, g, sq, d)
    rows = _positions(rank, num_ranks, sq, zigzag)
    keep0 = _keep_from_bias(kbias)
    delta = jnp.sum(
        do5.astype(jnp.float32) * o5.astype(jnp.float32), axis=-1
    )  # (b, h_kv, g, sq)

    zeros_k = jnp.zeros(k.shape, jnp.float32)
    zeros_v = jnp.zeros(v.shape, jnp.float32)
    dq0 = jnp.zeros(q5.shape, jnp.float32)
    # step 0 on the resident chunk
    dk0, dv0, dq = _chunk_bwd_update(
        q5, do5, delta, lse, k, v, zeros_k, zeros_v, dq0, scale, rows, rows,
        causal, block_size, window, keep0,
    )

    def step(carry, t):
        (kc, vc, biasc, dkc, dvc), dq = carry
        # dK/dV ride the ring with their chunks
        kc, vc, biasc, dkc, dvc = _rotate(
            (kc, vc, biasc, dkc, dvc), axis_name
        )
        src = jax.lax.rem(rank - t + num_ranks, num_ranks)
        cols = _positions(src, num_ranks, sq, zigzag)
        keep_c = _keep_from_bias(biasc) if kbias is not None else None
        contributes = _chunk_contributes(rows, cols, causal, window,
                                         2 if zigzag else 1)
        if keep_c is not None:
            contributes = jnp.logical_and(contributes, jnp.any(keep_c))
        dkc, dvc, dq = jax.lax.cond(
            contributes,
            lambda ops: _chunk_bwd_update(
                q5, do5, delta, lse, kc, vc, ops[0], ops[1], ops[2], scale,
                rows, cols, causal, block_size, window, keep_c,
            ),
            lambda ops: ops,
            (dkc, dvc, dq),
        )
        return ((kc, vc, biasc, dkc, dvc), dq), None

    bias_carry = (kbias if kbias is not None
                  else _bias_placeholder(b, axis_name))
    carry = ((k, v, bias_carry, dk0, dv0), dq)
    if num_ranks > 1:
        from apex_tpu.parallel.utils import pvary_params

        carry = pvary_params(carry, axis_name)  # see fwd: carry fixed point
        with xlax.scaled(num_ranks - 1):  # see fwd: P-1 rotations
            carry, _ = jax.lax.scan(step, carry, jnp.arange(1, num_ranks))
    (kc, vc, _, dk, dv), dq = carry
    # one homing rotation: after P-1 rotations the accumulators sit one rank
    # short of their owners
    if num_ranks > 1:
        dk, dv = _rotate((dk, dv), axis_name)
    dkbias = None if kbias is None else jnp.zeros_like(kbias)
    return (dq.reshape(b, h, sq, d).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), dkbias)


_ring.defvjp(_ring_fwd_res, _ring_bwd)


def ring_attention(
    q,
    k,
    v,
    axis_name: str = "cp",
    causal: bool = False,
    scale: float = None,
    block_size: int = 512,
    window: int = None,
    zigzag: bool = False,
    key_padding_mask=None,
):
    """Exact sequence-sharded attention over the ``axis_name`` ring.

    q: (batch, heads, seq_local, head_dim); k, v: (batch, kv_heads,
    seq_local, head_dim) with heads % kv_heads == 0 (GQA/MQA: the ring
    rotates the GROUPED K/V, heads/kv_heads x less ICI traffic than
    repeating keys before the ring) — the local chunk of a sequence
    sharded over the cp axis. Call inside ``shard_map``.
    ``block_size`` bounds the K/V slice processed at once (local memory
    O(seq_local x block_size)). Returns the local output chunk; grads flow
    through a second ring pass (see module docstring).

    ``window`` (sliding-window, causal only) bands attention in GLOBAL
    positions across the ring's chunks — long-context mistral-style
    attention sharded over cp.

    ``key_padding_mask``: (batch, seq_local) bool, True = padded-out key —
    the LOCAL shard of the global padding mask, sharded exactly like k/v
    (zigzag-reordered with ``zigzag_shard`` when zigzag=True). It rotates
    around the ring with its K/V chunk, and an all-padded visiting chunk
    is skipped entirely like an out-of-band one.

    ``zigzag`` (causal load balance): shards carry pieces (r, 2P-1-r) of
    the sequence instead of contiguous chunks — prepare them with
    ``zigzag_shard`` and restore outputs with ``zigzag_unshard``. Under
    contiguous causal sharding, rank r touches r+1 chunks per pass while
    the masks kill the rest, so late ranks dominate the lockstep ring;
    zigzag gives every rank one early and one late piece, equalizing
    per-rotation work (~2x less wasted compute at large P).
    """
    if window is not None and not causal:
        raise ValueError("window requires causal=True (mistral semantics)")
    if zigzag and q.shape[-2] % 2:
        raise ValueError("zigzag needs an even per-rank sequence length")
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"heads ({q.shape[1]}) not divisible by kv_heads ({k.shape[1]})"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    kbias = None
    if key_padding_mask is not None:
        if key_padding_mask.shape != (q.shape[0], k.shape[2]):
            raise ValueError(
                f"key_padding_mask {key_padding_mask.shape} != "
                f"(batch, seq_local) = {(q.shape[0], k.shape[2])}"
            )
        # float carrier (0 valid / -inf padded) so the mask can be a
        # differentiable custom_vjp primal with a zero cotangent
        kbias = jnp.where(key_padding_mask, _NEG_INF, 0.0).astype(jnp.float32)
    return _ring(q, k, v, kbias, axis_name, causal, scale, block_size,
                 window, zigzag)


def _zigzag_index(s: int, num_ranks: int):
    """Permutation placing pieces (r, 2P-1-r) consecutively for each r —
    the single source of the zigzag order for shard AND unshard."""
    if s % (2 * num_ranks):
        raise ValueError(
            f"sequence ({s}) not divisible by 2*cp ({2 * num_ranks})"
        )
    half = s // (2 * num_ranks)
    return jnp.concatenate([
        jnp.concatenate([
            r * half + jnp.arange(half),
            (2 * num_ranks - 1 - r) * half + jnp.arange(half),
        ])
        for r in range(num_ranks)
    ])


def zigzag_shard(x, num_ranks: int, axis: int = -2):
    """Reorder a GLOBAL sequence axis so a contiguous cp shard hands rank r
    the zigzag pieces (r, 2P-1-r). Apply before sharding inputs (and to
    targets/position ids that must stay aligned); invert with
    ``zigzag_unshard``."""
    return jnp.take(x, _zigzag_index(x.shape[axis], num_ranks), axis=axis)


def zigzag_unshard(x, num_ranks: int, axis: int = -2):
    """Inverse of ``zigzag_shard`` on the same global axis."""
    inv = jnp.argsort(_zigzag_index(x.shape[axis], num_ranks))
    return jnp.take(x, inv, axis=axis)


def ulysses_attention(
    q,
    k,
    v,
    axis_name: str = "cp",
    causal: bool = False,
    scale: float = None,
    window: int = None,
    attn_fn=None,
    key_padding_mask=None,
):
    """DeepSpeed-Ulysses-style attention: all-to-all from sequence-sharded
    to head-sharded, full-sequence local attention, all-to-all back.

    q: (batch, heads, seq_local, head_dim); k, v may carry fewer (GQA)
    heads — both counts must be divisible by the cp size (each rank keeps
    whole query groups, so the local attention stays a plain GQA call).
    ``attn_fn(q, k, v, causal=..., scale=...)`` defaults to the Pallas
    flash kernel. The two all_to_alls transpose to their own inverses
    under autodiff, so no custom backward is needed.

    ``key_padding_mask``: (batch, seq_local) bool local shard (True =
    padded) — all-gathered over cp (cheap: bytes per key, vs the d-dim
    K/V that ride the all_to_alls) so each head-sharded rank masks the
    full sequence it now sees.
    """
    if attn_fn is None:
        from apex_tpu.ops.attention import flash_attention

        attn_fn = flash_attention
    num_ranks = xlax.axis_size(axis_name)  # static inside shard_map
    assert q.shape[1] % num_ranks == 0, (
        f"heads ({q.shape[1]}) not divisible by cp size ({num_ranks}); "
        "use ring_attention for head counts below the cp degree"
    )
    assert k.shape[1] % num_ranks == 0, (
        f"kv_heads ({k.shape[1]}) not divisible by cp size ({num_ranks}); "
        "use ring_attention for grouped-KV head counts below the cp degree"
    )

    # With cp=1 this degrades to plain attention.
    def to_heads(x):
        # (b, h, s_loc, d) -> (b, h/P, s_glob, d)
        return xlax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def to_seq(x):
        return xlax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    # heads are sharded but each rank sees the FULL sequence, so the local
    # attention supports windows natively
    kw = {} if window is None else {"window": window}
    if key_padding_mask is not None:
        kw["key_padding_mask"] = xlax.all_gather(
            key_padding_mask, axis_name, axis=1, tiled=True
        )
    oh = attn_fn(qh, kh, vh, causal=causal, scale=scale, **kw)
    return to_seq(oh)


def cp_decode_attention(q, k, v, padded, axis_name: str, scale=None):
    """Single-token decode attention over a context-parallel KV cache.

    The decode-time counterpart of :func:`ring_attention` (extension — the
    reference has no inference path): each rank holds a shard of the KV
    cache, the one new query token is replicated over ``axis_name``, and
    the per-rank partial softmax stats merge with the flash/ring
    log-sum-exp identity via one ``pmax`` + two ``psum``s.  Per decode
    step that is O(1) collective latency instead of re-gathering the
    cache, and each rank's compute is O(L_local) — long-context decode
    scales across the mesh exactly like the ring trains it.

    Args:
      q: (b, h, 1, d), replicated over ``axis_name``.
      k, v: (b, h_kv, L_local, d) — this rank's cache shard (GQA: h must
        be a multiple of h_kv; consecutive grouping, q_head // g).
      padded: (b, L_local) bool, True = slot holds no valid key (unwritten
        tail, out-of-window, or another rank's turn in a round-robin
        layout).
      scale: softmax scale, default 1/sqrt(d) (flash_attention's default).

    Returns (b, h, 1, d), replicated over ``axis_name``.
    """
    b, h, sq, d = q.shape
    if sq != 1:
        raise ValueError(f"cp_decode_attention is single-token (sq={sq})")
    h_kv = k.shape[1]
    if h % h_kv:
        raise ValueError(f"GQA heads {h} not a multiple of kv heads {h_kv}")
    g = h // h_kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32).reshape(b, h_kv, g, d)
    s = jnp.einsum("bhgd,bhld->bhgl", qf, k.astype(jnp.float32)) * scale
    pad = padded[:, None, None, :]
    s = jnp.where(pad, _NEG_INF, s)
    m = jnp.max(s, axis=-1, keepdims=True)  # (b, h_kv, g, 1)
    p = jnp.where(pad, 0.0, jnp.exp(s - m))  # all-padded shard: p == 0
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgl,bhld->bhgd", p, v.astype(jnp.float32))
    m_g = xlax.pmax(m, axis_name)
    alpha = jnp.exp(m - m_g)  # -> 0 for shards far below the global max
    l_g = xlax.psum(l * alpha, axis_name)
    o_g = xlax.psum(o * alpha, axis_name) / l_g
    return o_g.reshape(b, h, 1, d).astype(q.dtype)
