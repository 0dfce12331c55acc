"""Flash attention — flagship Pallas kernel #2.

Reference parity: supersedes both ``fmhalib`` (contrib/fmha — seq<=512,
head_dim 64 MLPerf BERT kernel) and ``fast_multihead_attn``
(contrib/multihead_attn — CUTLASS fused MHA): a single blockwise
online-softmax attention kernel with no sequence-length cap.

Design: forward is a Pallas kernel — grid over (batch*heads, q_blocks), K/V
resident in VMEM per (b,h), online softmax accumulation in fp32, causal
blocks skipped entirely via a data-dependent ``fori_loop`` bound. The
backward is two Pallas kernels (dq over q blocks; dk/dv over kv blocks)
that recompute probabilities from the saved logsumexp per block pair —
the standard flash recompute strategy, O(seq x block) memory in both
directions.

Tiles: each kernel's (block_q, block_k) follows the call's shape
(``_flash_tiles``), not one constant. A kernel is latency-bound when the
step of its loop is narrow, so every side takes the widest of 1024, 512,
256, 128 that divides its sequence and that the VMEM beside the
double-buffered resident pair has room for: 1024 x 1024 at GPT-2's
(seq 1024, head_dim 64), back to 128 x 128 at the residency edge, under
128 keys, or under a narrow sliding window. Inside a tile the loop gives
the scheduler independent work (``_sweep``): blocks every row sees whole
run with no mask code; the blocks on the diagonal are unrolled at trace
time, each ``_SUBTILE`` rows taking the keys it sees whole unmasked, one
masked square, and nothing above it; running max and sum live lane-dense
in VMEM scratch; the dk/dv kernel works on transposed scores
(``k . q^T``), so its products are plain and lse/delta broadcast as the
rows they arrive as. ``block_q`` / ``block_k`` force one tile everywhere
(the tests' small tiles). The tiles ride in each call's
``kernel_metadata`` beside the kernel's name.

Latent attention (``latent_flash_attention``): the same three kernels on
the operands its projections write, batch-major rows with (head, d) in the
lanes. A head is a 128-lane column range of those arrays, not a leading
index of a transposed copy, and the score is a sum over parts (no-rope,
rope): the kernels' bodies loop over a list of (q part, k part) that has
one member for ``flash_attention`` (``_operands``). One more kernel,
``mla_rope``, rotates the queries' rope part where it lies.

Serving decode (``paged_decode_attention``): one query token a lane against
the serving engine's block pool (``serving/kvcache.py``), read where it lies
by block table and length. Heads ride in the lanes here too: a pool block
is (block_size, h_kv * hd), one contiguous copy, and each query head sits
in its kv head's lanes of a (heads, h_kv * hd) operand, so one product a
chunk of keys scores every head. Forward only (no gradient flows through a
decode step).

Single-chip long context: K/V residency caps the kernel at
``_KV_RESIDENT_BYTES`` (below 14k bf16 / 7k fp32 keys at head_dim <= 128).
Beyond it — or when the XLA fallback's full (sq, sk) score tensor would blow
``_SCORE_BYTES`` — dispatch switches to ``_attn_blockwise``: an XLA-level
(cq, ck)-tiled online softmax with a custom lse-recompute VJP, the same
math as the kernel one tile size up, supporting GQA, key-padding masks,
sliding windows, and rectangular causal. ``impl="blockwise"`` forces it.

Long-context across chips is handled one level up by
``apex_tpu.parallel.ring_attention``, which rotates K/V chunks over the
cp ring with this same online-softmax structure per visiting chunk.
"""

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.monitor.goodput.scopes import kernel_metadata, model_scope
from apex_tpu.ops._dispatch import resolve_impl
from apex_tpu.ops.rope import apply_rotary_pos_emb

_NEG_INF = -1e30
# rows of a tile that one unrolled step of a kernel's loop handles
_SUBTILE = 256


def _causal_hi(qi, bq: int, bk: int, num_kv, offs: int = 0):
    """Last kv block (exclusive) participating for q block ``qi`` under the
    causal mask — shared by the fwd/bwd kernels (offs=0) and the blockwise
    path (offs = sk - sq, bottom-right alignment)."""
    return jnp.minimum(jax.lax.div((qi + 1) * bq + offs - 1, bk) + 1, num_kv)


def _band_keep(delta, shape, window=None, q_axis: int = 0):
    """Keep-mask (True = attend) of a score tile whose first query sits
    ``delta`` keys after its first key: key - query <= delta, and with a
    sliding ``window`` W also > delta - W. Queries run along ``q_axis``
    (1 for the dk/dv kernel's transposed scores)."""
    ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
             - jax.lax.broadcasted_iota(jnp.int32, shape, q_axis))
    keep = ahead <= delta
    if window is not None:
        keep = jnp.logical_and(keep, ahead > delta - window)
    return keep


def _causal_keep(qi, kj, bq: int, bk: int, window=None, offs: int = 0):
    """(bq, bk) keep-mask (True = attend) for block pair (qi, kj); with a
    sliding ``window`` W, each row attends to cols in (row - W, row]. Query
    row r sits at global key position r + offs."""
    return _band_keep(qi * bq + offs - kj * bk, (bq, bk), window)


def _window_lo(qi, bq: int, bk: int, window, offs: int = 0):
    """First kv block (inclusive) a windowed-causal q block touches."""
    return jnp.maximum(0, jax.lax.div(qi * bq + offs - window + 1, bk))


def _q_band(kj, bq: int, bk: int, num_q, causal: bool, window, offs: int = 0):
    """[lo, hi) q-block range whose band intersects kv block ``kj`` — the
    transpose of (_window_lo, _causal_hi); shared by the dkv kernel
    (offs=0) and the blockwise dk/dv pass."""
    lo = (
        jnp.maximum(0, jax.lax.div(kj * bk - offs, bq)) if causal else 0
    )
    hi = (
        jnp.minimum(num_q, jax.lax.div(kj * bk + bk + window - 2 - offs, bq) + 1)
        if window is not None
        else num_q
    )
    return lo, hi


def window_mask(sq: int, sk: int, window: int):
    """(sq, sk) bool mask, True = BEYOND the sliding window's lower edge
    (col <= row - window, bottom-right aligned like causal_mask). The single
    source of the band formula for the fused kernels' XLA fallback and the
    unfused CoreAttention path."""
    return (
        jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None] + (sk - sq) - window
    )


def causal_mask(sq: int, sk: int):
    """(sq, sk) bool mask, True = masked out. Bottom-right aligned for
    rectangular scores (sk > sq ⇒ the query block sits at the end of the
    key sequence — the KV-cache / blockwise convention)."""
    return jnp.arange(sk)[None, :] > jnp.arange(sq)[:, None] + (sk - sq)


def _attn_ref(q, k, v, scale, causal, mask=None, window=None):
    """Plain XLA attention; q: (B, H, S, D); k/v: (B, H_kv, S, D) with
    H % H_kv == 0 (GQA: each kv head serves H/H_kv query heads)."""
    h, h_kv = q.shape[1], k.shape[1]
    if h_kv != h:
        k = jnp.repeat(k, h // h_kv, axis=1)
        v = jnp.repeat(v, h // h_kv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        s = jnp.where(causal_mask(s.shape[-2], s.shape[-1]), _NEG_INF, s)
    if window is not None:
        s = jnp.where(window_mask(s.shape[-2], s.shape[-1], window), _NEG_INF, s)
    if mask is not None:
        s = jnp.where(mask, _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)
    # fully-masked rows (e.g. the whole sliding window padded out) must be
    # ZERO, not uniform-softmax leakage over equal -1e30 scores — the same
    # dead-row contract as the Pallas kernel and the blockwise/ring paths
    dead = jnp.all(s <= _NEG_INF * 0.5, axis=-1, keepdims=True)
    return jnp.where(dead, jnp.zeros((), out.dtype), out)


_NT = (((1,), (1,)), ((), ()))  # a · b^T
_NN = (((1,), (0,)), ((), ()))  # a · b


def _dot(a, b, dims):
    # dot operands KEEP the input dtype (bf16 stays bf16) with fp32
    # accumulation via preferred_element_type — upcasting operands to fp32
    # before the dot forces the MXU's slow fp32 path; softmax math stays
    # fp32 throughout
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _lanes(x, n: int):
    """A per-row statistic against ``n`` columns. The kernels carry m, l,
    lse and delta ``(rows, 128)`` with every lane holding the row's value
    (or ``(rows, 1)`` when a tile is no multiple of 128): widening such an
    array is a re-use of its vregs, where a ``(rows, 1)`` column costs a
    lane broadcast per use."""
    w = x.shape[1]
    if w == 1 or w == n:
        return x
    if n < w:
        return x[:, :n]
    if n % w == 0:
        return jnp.tile(x, (1, n // w))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _subtile(tile: int) -> int:
    """Rows of a tile one unrolled step handles: sub-tiles of ``_SUBTILE``
    are independent chains (matmul, reduce, exp, matmul) the scheduler can
    overlap; a tile that is no multiple is one sub-tile."""
    return _SUBTILE if tile % _SUBTILE == 0 else tile


def _stat_lanes(sub: int, width: int) -> int:
    """Lanes the row statistics of a ``sub``-row sub-tile are kept on
    (see _lanes): 128 where every score block is whole vregs wide."""
    return 128 if sub % 8 == 0 and width % 128 == 0 else 1


def _kv_spans(qi, bq: int, bk: int, num_kv, causal: bool, window):
    """lo <= a <= b <= hi for q block ``qi``: kv blocks [lo, a) cross the
    window's lower edge, [a, b) are visible to every row of the block (no
    mask code at all), [b, hi) cross the diagonal."""
    if not causal:
        return 0, 0, num_kv, num_kv
    hi = _causal_hi(qi, bq, bk, num_kv)
    lo = _window_lo(qi, bq, bk, window) if window is not None else 0
    b = jnp.clip(jax.lax.div(qi * bq + 1, bk), lo, hi)
    if window is None:
        return lo, lo, b, hi
    first = jax.lax.div(jnp.maximum(qi * bq + bq - window, 0) + bk - 1, bk)
    return lo, jnp.clip(first, lo, b), b, hi


def _q_spans(kj, bq: int, bk: int, num_q, causal: bool, window):
    """The transpose of ``_kv_spans`` for kv block ``kj``: q blocks [lo, a)
    cross the diagonal, [a, b) see every key of the block, [b, hi) cross
    the window's lower edge."""
    if not causal:
        return 0, 0, num_q, num_q
    lo, hi = _q_band(kj, bq, bk, num_q, causal, window)
    a = jnp.clip(jax.lax.div(kj * bk + bk + bq - 2, bq), lo, hi)
    if window is None:
        return lo, a, hi, hi
    return lo, a, jnp.clip(jax.lax.div(kj * bk + window, bq), a, hi), hi


def _static_diagonal(causal: bool, window, tile: int, step: int, sub: int):
    """Whether the blocks that cross the diagonal sit at offsets known at
    trace time: a causal square without a window, the program's tile a
    multiple of the loop's step, the step a multiple of the sub-tile. Then
    each sub-tile takes the part of a diagonal block it sees whole without
    a mask, one masked square on the diagonal, and skips the rest."""
    return causal and window is None and tile % step == 0 and step % sub == 0


def _sweep(spans, edge, full, diag=None, n_diag: int = 0, diag_first=False):
    """Run a program's loop over blocks [lo, hi) of ``spans`` = (lo, a, b,
    hi): ``full(j)`` over the fully visible blocks, ``edge(j)`` over those
    that need the band mask. With ``diag`` (_static_diagonal) the ``n_diag``
    blocks that cross the diagonal are unrolled as ``diag(j, c)`` instead,
    at the end of the range (forward, dq) or at its start (dk/dv), and
    everything else is fully visible."""
    def loop(lo, hi, body):
        def step(j, carry):
            body(j)
            return carry

        jax.lax.fori_loop(lo, hi, step, 0)

    lo, a, b, hi = spans
    if diag is None:
        loop(lo, a, edge)
        loop(a, b, full)
        loop(b, hi, edge)
    elif diag_first:
        for c in range(n_diag):
            diag(lo + c, c)
        loop(lo + n_diag, hi, full)
    else:
        loop(lo, hi - n_diag, full)
        for c in range(n_diag):
            diag(hi - n_diag + c, c)


def _sweep_q_tile(visit, qi, bq, bk, sub, num_kv, causal, window):
    """The forward's and dq's loop for q block ``qi``: ``visit(r, j, off,
    w, keep)`` takes q sub-tile ``r`` against the ``w`` keys from ``off``
    into kv block ``j``, under the keep-mask ``keep`` or none."""
    subs = range(bq // sub)

    def edge(j):
        for r in subs:
            visit(r, j, 0, bk, _band_keep(
                qi * bq + r * sub - j * bk, (sub, bk), window))

    def full(j):
        for r in subs:
            visit(r, j, 0, bk, None)

    def diag(j, c):
        for r in subs:
            off = r * sub - c * bk  # where this sub-tile's diagonal starts
            if off > 0:
                visit(r, j, 0, min(off, bk), None)
            if 0 <= off < bk:
                visit(r, j, off, sub, _band_keep(0, (sub, sub)))

    if _static_diagonal(causal, window, bq, bk, sub):
        n_diag = bq // bk
        _sweep((0, 0, 0, (qi + 1) * n_diag), edge, full, diag, n_diag)
    else:
        _sweep(_kv_spans(qi, bq, bk, num_kv, causal, window), edge, full)


def _head_lanes(shape, rope: int, g):
    """Keep-mask of head ``g``'s lanes in a block that packs the ``rope``
    wide parts of ``shape[1] // rope`` consecutive heads side by side."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jax.lax.div(lane, rope) == jax.lax.rem(g, shape[1] // rope)


def _operands(refs, latent, q_side: bool):
    """A kernel's leading refs as reads of rows: ``(q_parts, k_parts, v,
    rest)``. The score of a block pair is the sum over the parts of
    ``q_part . k_part^T``; a part and ``v`` are ``rows -> (n, width)``
    reads, ``rest`` the refs that follow.

    ``latent`` None: ``q, k, v`` blocks of (batch * heads, seq, d) arrays,
    one part. ``latent = (nope, rope)``: ``q_nope, q_rope, kv, k_rope``
    blocks of what latent attention's projections wrote
    (``latent_flash_attention``), two parts: head h's no-rope key and
    value are the two lane ranges of ONE ``kv`` block, and the rope parts
    come ``128 // rope`` heads to a block: the query's of neighbouring
    heads, the one shared key's repeated. So a contraction over the whole
    block is this head's alone once the other heads' lanes are zero on one
    side: the side this program holds a block of (``q_side``: the query in
    forward and dq, the key in dk/dv), zeroed once into the trailing
    scratch ref."""
    if latent is None:
        q_ref, k_ref, v_ref = refs[:3]
        return ([lambda r: q_ref[0, r, :]], [lambda r: k_ref[0, r, :]],
                lambda r: v_ref[0, r, :], refs[3:])
    nope, rope = latent
    qn_ref, qr_ref, kv_ref, kr_ref = refs[:4]
    held = refs[-1]
    block = (qr_ref if q_side else kr_ref)[0]
    held[...] = jnp.where(
        _head_lanes(block.shape, rope, pl.program_id(0)), block,
        jnp.zeros_like(block))
    q_rope = (lambda r: held[r, :]) if q_side else (lambda r: qr_ref[0, r, :])
    k_rope = (lambda r: kr_ref[0, r, :]) if q_side else (lambda r: held[r, :])
    return ([lambda r: qn_ref[0, r, :], q_rope],
            [lambda r: kv_ref[0, r, :nope], k_rope],
            lambda r: kv_ref[0, r, nope:], refs[4:-1])


def _score(a_parts, b_parts):
    """sum over the parts of ``a . b^T``, fp32."""
    return functools.reduce(
        operator.add, [_dot(a, b, _NT) for a, b in zip(a_parts, b_parts)])


def _store_row(row_ref, col, n: int):
    """A per-row statistic of ``n`` rows, (n, 128) lane-dense or (n, 1),
    into its lane-major (1, 1, n) block."""
    if col.shape[1] == 128 and n % 128 == 0:
        # rows -> lanes through the XLU: every row of a lane-dense
        # square's transpose is the column as a row
        for c in range(n // 128):
            at = slice(c * 128, (c + 1) * 128)
            row_ref[0, :, at] = col[at, :].T[:1, :]
    else:
        row_ref[0, 0, :] = col[:, 0]


def _load_row(row_ref, col_ref, n: int):
    """``_store_row``'s way back: a lane-major (1, 1, n) block into the
    (n, 128) lane-dense or (n, 1) scratch a kernel's loop reads."""
    if col_ref.shape[1] == 128 and n % 128 == 0:
        for c in range(n // 128):  # lanes -> rows through the XLU
            at = slice(c * 128, (c + 1) * 128)
            col_ref[at, :] = jnp.broadcast_to(
                row_ref[0, :, at], (128, 128)).T
    else:
        col_ref[...] = jnp.broadcast_to(
            row_ref[0, 0, :][:, None], col_ref.shape)


def _flash_fwd_kernel(*refs, scale, causal, bq, bk, sub, has_kpm,
                      window=None, latent=None):
    q_parts, k_parts, value, rest = _operands(refs, latent, q_side=True)
    kpm_ref = rest[0] if has_kpm else None  # (1, SK/BK, BK), 1 = padded
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest[-5:]
    d = acc_ref.shape[1]  # the output is as wide as v (d_v), q and k d_qk
    qi = pl.program_id(1)
    num_kv = refs[2].shape[1] // bk  # k (latent: kv) is resident

    # running statistics live in VMEM scratch, lane-dense (see _lanes)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def visit(r, j, off, w, keep):
        """q sub-tile ``r`` against keys [j*bk + off, j*bk + off + w)."""
        rows = pl.ds(r * sub, sub)
        keys = pl.ds(j * bk + off, w)
        vb = value(keys)
        s = _score([q(rows) for q in q_parts],
                   [k(keys) for k in k_parts]) * scale  # (sub, w), fp32
        if keep is not None:
            s = jnp.where(keep, s, _NEG_INF)
        if has_kpm:
            pad = kpm_ref[0, pl.ds(j, 1), off:off + w]
            s = jnp.where(pad == 0, s, _NEG_INF)
        m = m_ref[rows, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - _lanes(m_new, w))
        l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
            p, axis=1, keepdims=True)
        acc_ref[rows, :] = acc_ref[rows, :] * _lanes(alpha, d) + _dot(
            p.astype(vb.dtype), vb, _NN)
        m_ref[rows, :] = m_new

    _sweep_q_tile(visit, qi, bq, bk, sub, num_kv, causal, window)

    # fully-masked rows (every key padded): the finite -1e30 mask means the
    # loop accumulated a spurious uniform softmax (p = exp(0) = 1 per key).
    # Emit ZEROS and a +1e30 lse sentinel instead: output-zero rows make the
    # backward's p = exp(s - lse) underflow to exactly 0, so the custom VJP
    # is self-consistent (o = 0 constant => dq = dk = dv = 0 for that row)
    # and no padded v values leak into the output. The XLA kpm path zeroes
    # dead rows identically (flash_attention wrapper).
    m = m_ref[...]
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = jnp.where(
        _lanes(m, d) <= _NEG_INF * 0.5, 0.0, acc_ref[...] / _lanes(l, d)
    ).astype(o_ref.dtype)
    _store_row(lse_ref,
               jnp.where(m <= _NEG_INF * 0.5, -_NEG_INF, m + jnp.log(l)), bq)


def _kpm_spec(heads, num_kv, bk):
    """Key-padding-mask block: this (b*h) grid step's mask out of the
    (b, sk/bk, bk) int32 mask, one row per kv block — heads is static, so
    b = bh // heads is an index-map affine. The block's last two dims EQUAL
    the array's, which the TPU lowering accepts at any batch (a (1, sk)
    block of a (b, sk) array is refused for every b > 1: 1 is neither b nor
    a multiple of 8), and a kernel reads kv block j's keys as row j — a
    dynamic sublane index, where slicing one long row at j*bk would need a
    dynamic LANE offset that Mosaic only takes in multiples of 128."""
    return pl.BlockSpec(
        (1, num_kv, bk), lambda b_h, i, heads=heads: (b_h // heads, 0, 0)
    )


def _kpm_blocks(kpm, bk):
    """The key-padding operand of a call, if any: (b, sk) int32 ->
    [(b, sk/bk, bk)], one row per kv block (_kpm_spec)."""
    return [] if kpm is None else [kpm.reshape(kpm.shape[0], -1, bk)]


@functools.lru_cache(maxsize=128)
def _flash_calls(bh, sq, sk, d, dv, dtypes, heads, group, scale, causal,
                 interpret, tile, subs, window, has_kpm, rope=0):
    """The three ``pallas_call``s (forward, dq, dk/dv) of one static
    configuration. Cached, because a model makes the same call once a
    layer and JAX traces and lowers a callable it has seen once, not once a
    layer: 24 layers x 3 kernels were 20 s of GPT-2 345M's set-up.

    ``rope`` 0: q and k are (bh, seq, ``d``) arrays, v and the output
    ``dv`` wide, a head a leading index. ``rope`` > 0, latent attention's
    operands as its projections wrote them (``_operands``), a head a lane
    range: q_nope (b, sq, heads * d), q_rope (b, sq, heads * rope), kv
    (b, sk, heads * (d + dv)) and the shared rope key 128 lanes wide
    (b, sk, 128); the output, dq_nope and dkv in the same layouts, and per
    head 128 lanes of dq_rope (every group of ``rope`` lanes holds it) and
    of the shared key's gradient (this head's part in its own lanes, zero
    beside them); dq computes delta from o and hands it to dk/dv."""
    bq, bk = tile
    sub_q, sub_k = subs
    q_dtype, k_dtype, v_dtype = dtypes
    lw = _stat_lanes(sub_q, bk)
    kernel_kw = dict(scale=scale, causal=causal, bq=bq, bk=bk,
                     has_kpm=has_kpm, window=window,
                     latent=(d, rope) if rope else None)
    kpm_spec = [_kpm_spec(heads, sk // bk, bk)] if has_kpm else []
    # lse and delta carry a singleton middle dim so their block (1, 1, bq)
    # satisfies the TPU (8, 128) tiling rule on the last two dims
    row_block = pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i))
    row_q = pl.BlockSpec((1, 1, sq), lambda b, j: (b, 0, 0))
    rows = jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32)
    stats = [pltpu.VMEM((bq, lw), jnp.float32)] * 2

    def spec(n, w, where, along=True):
        """(1, n, w) blocks of a (batch-like, seq, lanes) array: program
        ``g`` reads batch row and column block ``where(g)``, sequence block
        ``i`` (``along``) or the whole sequence as one resident block."""
        return pl.BlockSpec((1, n, w), lambda g, i: (
            where(g)[0], i if along else 0, where(g)[1]))

    if rope:
        b, rw = bh // heads, 128
        per = rw // rope  # heads to a block of rope parts
        tiles = dict(block_q=bq, block_k=bk, d_nope=d, d_rope=rope, d_v=dv)
        # a head's lanes are a column block: its own, the one its rope part
        # shares with its neighbours, the one shared rope key's
        own = lambda g: (g // heads, g % heads)
        pair = lambda g: (g // heads, g % heads // per)
        shared = lambda g: (g // heads, 0)
        q_in = [spec(bq, d, own), spec(bq, rw, pair)]
        k_res = [spec(sk, d + dv, own, False), spec(sk, rw, shared, False)]
        q_res = [spec(sq, d, own, False), spec(sq, rw, pair, False)]
        k_in = [spec(bk, d + dv, own), spec(bk, rw, shared)]
        wide = lambda s: jax.ShapeDtypeStruct((b, s, heads * rw), q_dtype)
        o_shape = jax.ShapeDtypeStruct((b, sq, heads * dv), q_dtype)
        # dq's third row operand is o (delta is computed from it and handed
        # on); dq_nope, every rope group of dq_rope, delta
        dq_third, dq_accs = spec(bq, dv, own), [(bq, d), (bq, rw)]
        dq_out = (spec(bq, d, own), spec(bq, rw, own), row_block)
        dq_shape = (jax.ShapeDtypeStruct((b, sq, heads * d), q_dtype),
                    wide(sq), rows)
        # dk_nope and dv as the two lane ranges of kv's gradient; this
        # head's part of the shared rope key's
        dkv_out = (spec(bk, d + dv, own), spec(bk, rw, own))
        dkv_shape = (jax.ShapeDtypeStruct((b, sk, heads * (d + dv)), k_dtype),
                     wide(sk))
        dkv_accs = [(bk, d), (bk, rw), (bk, dv)]
        held = lambda n: [pltpu.VMEM((n, rw), q_dtype)]
    else:
        tiles = dict(block_q=bq, block_k=bk, d_qk=d, d_v=dv)
        own = lambda g: (g, 0)
        # GQA: q-head row bh maps to kv row bh // group (group = h // h_kv,
        # static). group == 1 recovers plain MHA indexing
        grouped = lambda g: (g // group, 0)
        q_in = [spec(bq, d, own)]
        k_res = [spec(sk, d, grouped, False), spec(sk, dv, grouped, False)]
        q_res = [spec(sq, d, own, False)]
        k_in = [spec(bk, d, grouped), spec(bk, dv, grouped)]
        o_shape = jax.ShapeDtypeStruct((bh, sq, dv), q_dtype)
        dq_third, dq_accs = row_block, [(bq, d)]
        dq_out = spec(bq, d, own)
        dq_shape = jax.ShapeDtypeStruct((bh, sq, d), q_dtype)
        # per-Q-HEAD partials: grid still runs over all bh q-head rows, so
        # two q heads sharing a kv head never race on one output block
        dkv_out = (spec(bk, d, own), spec(bk, dv, own))
        dkv_shape = (jax.ShapeDtypeStruct((bh, sk, d), k_dtype),
                     jax.ShapeDtypeStruct((bh, sk, dv), v_dtype))
        dkv_accs = [(bk, d), (bk, dv)]
        held = lambda n: []
    o_block, do_res = spec(bq, dv, own), spec(sq, dv, own, False)

    vmem = lambda shapes: [pltpu.VMEM(s, jnp.float32) for s in shapes]
    fwd = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, sub=sub_q, **kernel_kw),
        out_shape=(o_shape, rows),
        grid=(bh, sq // bq),
        in_specs=q_in + k_res + kpm_spec,
        out_specs=(o_block, row_block),
        scratch_shapes=vmem([(bq, dv)]) + stats + held(bq),
        interpret=interpret,
        metadata=kernel_metadata("flash_fwd", **tiles),
    )
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sub=sub_q, **kernel_kw),
        out_shape=dq_shape,
        grid=(bh, sq // bq),
        # q block; k, v resident; do block; lse block; delta block (latent:
        # o block)
        in_specs=q_in + k_res + [o_block, row_block, dq_third] + kpm_spec,
        out_specs=dq_out,
        scratch_shapes=vmem(dq_accs) + stats + held(bq),
        interpret=interpret,
        metadata=kernel_metadata("flash_bwd_dq", **tiles),
    )
    dkv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sub=sub_k, **kernel_kw),
        out_shape=dkv_shape,
        grid=(bh, sk // bk),
        # q resident; k, v blocks (grouped); do resident; lse, delta rows
        in_specs=q_res + k_in + [do_res, row_q, row_q] + kpm_spec,
        out_specs=dkv_out,
        scratch_shapes=vmem(dkv_accs) + held(bk),
        interpret=interpret,
        metadata=kernel_metadata("flash_bwd_dkv", **tiles),
    )
    return fwd, dq, dkv


def _calls_for(q3, kv3, kpm, heads, group, scale, causal, interpret, tile,
               window):
    k3, v3 = kv3
    bh, sq, d = q3.shape
    return _flash_calls(
        bh, sq, k3.shape[1], d, v3.shape[2], (q3.dtype, k3.dtype, v3.dtype),
        heads, group, scale, causal, interpret, tile,
        tuple(_subtile(t) for t in tile), window, kpm is not None)


def _flash_fwd(q3, kv3, kpm, heads, group, scale, causal, interpret, tile,
               window):
    fwd, _, _ = _calls_for(
        q3, kv3, kpm, heads, group, scale, causal, interpret, tile, window)
    o, lse = fwd(q3, *kv3, *_kpm_blocks(kpm, tile[1]))
    return o, lse.reshape(q3.shape[:2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q3, kv3, kpm, heads, group, scale, causal, interpret, tile, window):
    o, _ = _flash_fwd_res(
        q3, kv3, kpm, heads, group, scale, causal, interpret, tile, window
    )
    return o


def _flash_fwd_res(q3, kv3, kpm, heads, group, scale, causal, interpret, tile,
                   window):
    o, lse = _flash_fwd(
        q3, kv3, kpm, heads, group, scale, causal, interpret, tile, window
    )
    return o, (q3, kv3, kpm, o, lse)


def _flash_bwd_dq_kernel(*refs, scale, causal, bq, bk, sub, has_kpm,
                         window=None, latent=None):
    """dq for one q block: loop over participating kv blocks (the exact
    recompute-from-lse strategy of the standard flash backward)."""
    q_parts, k_parts, value, rest = _operands(refs, latent, q_side=True)
    # do, lse, then delta's row (latent: the o block delta is made from)
    do_ref, lse_ref, third = rest[:3]
    kpm_ref = rest[3] if has_kpm else None
    n = len(k_parts)  # one dq and one accumulator a part
    outs, acc_refs, (lse_c, delta_c) = (
        rest[3 + has_kpm:-(n + 2)], rest[-(n + 2):-2], rest[-2:])
    qi = pl.program_id(1)
    num_kv = refs[2].shape[1] // bk

    for acc_ref in acc_refs:
        acc_ref[...] = jnp.zeros_like(acc_ref)
    # lse and delta arrive lane-major; one move to rows a program, not one
    # an iteration
    _load_row(lse_ref, lse_c, bq)
    if latent is None:
        _load_row(third, delta_c, bq)
    else:
        # delta = rowsum(dO * O) from this program's own blocks, kept for
        # the loop and handed on to the dk/dv kernel as a row
        delta_c[...] = jnp.broadcast_to(jnp.sum(
            do_ref[0].astype(jnp.float32) * third[0].astype(jnp.float32),
            axis=1, keepdims=True), delta_c.shape)
        _store_row(outs[n], delta_c[...], bq)

    def visit(r, j, off, w, keep):
        rows = pl.ds(r * sub, sub)
        keys = pl.ds(j * bk + off, w)
        kbs = [k(keys) for k in k_parts]
        vb = value(keys)
        s = _score([q(rows) for q in q_parts], kbs) * scale
        p = jnp.exp(s - _lanes(lse_c[rows, :], w))
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        if has_kpm:
            pad = kpm_ref[0, pl.ds(j, 1), off:off + w]
            p = jnp.where(pad == 0, p, 0.0)
        dp = _dot(do_ref[0, rows, :], vb, _NT)
        ds = p * (dp - _lanes(delta_c[rows, :], w)) * scale
        for acc_ref, kb in zip(acc_refs, kbs):
            acc_ref[rows, :] += _dot(ds.astype(kb.dtype), kb, _NN)

    _sweep_q_tile(visit, qi, bq, bk, sub, num_kv, causal, window)
    for dq_ref, acc_ref in zip(outs, acc_refs):
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, scale, causal, bq, bk, sub, has_kpm,
                          window=None, latent=None):
    """dk/dv for one kv block: loop over participating q blocks, on the
    TRANSPOSED scores s_t = k · q^T (keys along sublanes, queries along
    lanes): dv += p_t · dO and dk += ds_t · q are plain products, and the
    lane-major lse and delta rows broadcast over the keys as they are."""
    q_parts, k_parts, value, rest = _operands(refs, latent, q_side=False)
    do_ref, lse_ref, delta_ref = rest[:3]
    kpm_ref = rest[3] if has_kpm else None
    n = len(k_parts)
    outs, accs = rest[3 + has_kpm:-(n + 1)], rest[-(n + 1):]
    dk_accs, dv_acc = accs[:n], accs[n]
    kj = pl.program_id(1)
    num_q = refs[0].shape[1] // bq  # q is resident

    for acc_ref in accs:
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(r, i, off, w, keep):
        """kv sub-tile ``r`` against queries [i*bq + off, i*bq + off + w)."""
        keys = pl.ds(r * sub, sub)
        rows = pl.ds(i * bq + off, w)
        qbs = [q(rows) for q in q_parts]
        vb = value(keys)
        dob = do_ref[0, rows, :]
        s_t = _score([k(keys) for k in k_parts], qbs) * scale  # (sub, w)
        p_t = jnp.exp(s_t - lse_ref[0, :, rows])
        if keep is not None:
            p_t = jnp.where(keep, p_t, 0.0)
        dv_acc[keys, :] += _dot(p_t.astype(dob.dtype), dob, _NN)
        dp_t = _dot(vb, dob, _NT)
        ds_t = p_t * (dp_t - delta_ref[0, :, rows]) * scale
        for dk_acc, qb in zip(dk_accs, qbs):
            dk_acc[keys, :] += _dot(ds_t.astype(qb.dtype), qb, _NN)

    def edge(i):
        for r in range(bk // sub):
            visit(r, i, 0, bq, _band_keep(
                i * bq - kj * bk - r * sub, (sub, bq), window, q_axis=1))

    def full(i):
        for r in range(bk // sub):
            visit(r, i, 0, bq, None)

    def diag(i, c):
        for r in range(bk // sub):
            off = r * sub - c * bq  # where this sub-tile's diagonal starts
            if off < 0:
                visit(r, i, 0, bq, None)
            elif off < bq:
                visit(r, i, off, sub, _band_keep(0, (sub, sub), q_axis=1))
                if off + sub < bq:
                    visit(r, i, off + sub, bq - off - sub, None)

    if _static_diagonal(causal, window, bk, bq, sub):
        n_diag = bk // bq
        _sweep((kj * n_diag, 0, 0, num_q), edge, full, diag, n_diag,
               diag_first=True)
    else:
        _sweep(_q_spans(kj, bq, bk, num_q, causal, window), edge, full)
    grads = [acc_ref[...] for acc_ref in accs]
    if has_kpm:
        # a key's dk and dv rows depend on that key's scores alone, so the
        # padded keys of THIS block are zeroed once here, not masked out
        # of every product above
        pad = kpm_ref[0, kj, :].astype(jnp.float32)[:, None]  # (bk, 1)
        grads = [jnp.where(pad == 0.0, g, 0.0) for g in grads]
    if latent is None:
        for out_ref, g in zip(outs, grads):
            out_ref[0] = g.astype(out_ref.dtype)
        return
    # the products with the UNMASKED rope queries left other heads' lanes
    # in the shared key's gradient: keep this head's
    (dk_nope, dk_rope, dv), (dkv_ref, dkr_ref) = grads, outs
    nope = latent[0]
    dkv_ref[0, :, :nope] = dk_nope.astype(dkv_ref.dtype)
    dkv_ref[0, :, nope:] = dv.astype(dkv_ref.dtype)
    dkr_ref[0] = jnp.where(
        _head_lanes(dk_rope.shape, latent[1], pl.program_id(0)), dk_rope, 0.0
    ).astype(dkr_ref.dtype)


def _flash_bwd(heads, group, scale, causal, interpret, tile, window, res, do):
    """Pallas flash backward: recompute p from the saved logsumexp per
    block pair — O(seq x block) memory like the forward, never the full
    (sq, sk) score matrix (previously an XLA einsum chain).

    GQA (group > 1): both kernels run per Q head with grouped K/V indexing;
    dk/dv come out as per-q-head partials and are group-summed afterwards."""
    q3, (k3, v3), kpm, o, lse = res
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    _, dq_call, dkv_call = _calls_for(
        q3, (k3, v3), kpm, heads, group, scale, causal, interpret, tile,
        window)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # (BH, SQ)
    inputs = [q3, k3, v3, do, lse.reshape(bh, 1, sq), delta.reshape(bh, 1, sq),
              *_kpm_blocks(kpm, tile[1])]
    dq = dq_call(*inputs)
    dk_p, dv_p = dkv_call(*inputs)
    if group > 1:
        # q-head row r = b*heads + kv*group + j  ->  sum over j
        bhkv = bh // group
        dk = dk_p.reshape(bhkv, group, sk, d).sum(axis=1).astype(k3.dtype)
        dv = dv_p.reshape(bhkv, group, sk, -1).sum(axis=1).astype(v3.dtype)
    else:
        dk, dv = dk_p, dv_p
    # kpm is an int mask: no cotangent (None == symbolic zero)
    return dq, (dk, dv), None


_flash.defvjp(_flash_fwd_res, _flash_bwd)


# ---------------------------------------------------------------------------
# Latent attention: the kernels on the projections' own outputs
# ---------------------------------------------------------------------------


def _rope_kernel(x_ref, cos_ref, lo_ref, hi_ref, o_ref, *, shift, rope,
                 gather):
    """Rotate every ``rope``-wide group of lanes of a (1, rows, lanes)
    block by its row's angles: lane j of a pair takes ``x[j] * cos[j] +
    x[j + shift] * lo[j] + x[j - shift] * hi[j]`` (``lo`` is -sin on a
    pair's first lane and 0 on its second, ``hi`` sin on the second: the
    rolls go through the XLU, nothing is sliced or concatenated).
    ``gather``: the input holds 128 lanes a head with the head's values in
    every group (the dq kernel's), and output block c takes the lanes of
    its own heads from their blocks first."""
    cos, lo, hi = cos_ref[...], lo_ref[...], hi_ref[...]
    per = 128 // rope
    lane_head = jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1), rope)
    for c in range(o_ref.shape[2] // 128):
        if gather:
            x = x_ref[0, :, c * per * 128:(c * per + 1) * 128].astype(
                jnp.float32)
            for j in range(1, per):
                at = (c * per + j) * 128
                x = jnp.where(lane_head == j, x_ref[0, :, at:at + 128].astype(
                    jnp.float32), x)
        else:
            x = x_ref[0, :, c * 128:(c + 1) * 128].astype(jnp.float32)
        y = (x * cos + pltpu.roll(x, 128 - shift, 1) * lo
             + pltpu.roll(x, shift, 1) * hi)
        o_ref[0, :, c * 128:(c + 1) * 128] = y.astype(o_ref.dtype)


@functools.lru_cache(maxsize=32)
def _rope_call(b, s, lanes, dtype, rope, shift, gather, interpret):
    """The rotation of a (b, s, ``lanes``) array of ``rope``-wide groups
    (``_rope_kernel``), tables (s, 128) fp32; with ``gather`` the input is
    (b, s, lanes * 128 // rope). Named: the module's nameless Mosaic calls
    are the three flash kernels, and the benchmark counts them."""
    rows = next((t for t in (256, 128, 64, 32, 16, 8) if s % t == 0), s)
    table = pl.BlockSpec((rows, 128), lambda n, i: (i, 0))
    block = lambda w: pl.BlockSpec((1, rows, w), lambda n, i: (n, i, 0))
    return pl.pallas_call(
        functools.partial(_rope_kernel, shift=shift, rope=rope,
                          gather=gather),
        out_shape=jax.ShapeDtypeStruct((b, s, lanes), dtype),
        grid=(b, s // rows),
        in_specs=[block(lanes * 128 // rope if gather else lanes)]
        + [table] * 3,
        out_specs=block(lanes),
        interpret=interpret,
        name="mla_rope",
        metadata=kernel_metadata("mla_rope"),
    )


def _rope_tables(freqs, rope: int, interleaved: bool):
    """(cos, lo, hi), each (s, 128) fp32, of ``_rope_kernel`` from the
    (s, rope) angles: 128 // rope groups side by side."""
    reps = (1, 128 // rope)
    cos, sin = jnp.tile(jnp.cos(freqs), reps), jnp.tile(jnp.sin(freqs), reps)
    lane = jnp.arange(128) % rope
    first = (lane % 2 == 0) if interleaved else (lane < rope // 2)
    return cos, jnp.where(first, -sin, 0.0), jnp.where(first, 0.0, sin)


def _latent_calls(q_nope, kv, rope, kpm, heads, scale, interpret, tile):
    b, s, width = q_nope.shape
    nope = width // heads
    return _flash_calls(
        b * heads, s, s, nope, kv.shape[2] // heads - nope,
        (q_nope.dtype, kv.dtype, kv.dtype), heads, 1, scale, True, interpret,
        tile, tuple(_subtile(t) for t in tile), None, kpm is not None, rope)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _latent(q_nope, q_rope, kv, k_rope, tables, kpm, heads, scale, shift,
            interpret, tile):
    o, _ = _latent_fwd_res(q_nope, q_rope, kv, k_rope, tables, kpm, heads,
                           scale, shift, interpret, tile)
    return o


def _latent_fwd_res(q_nope, q_rope, kv, k_rope, tables, kpm, heads, scale,
                    shift, interpret, tile):
    """``k_rope`` arrives rotated (it is 1/heads of q's), ``q_rope`` not:
    its rotated copy is an operand of the kernels alone."""
    b, s, lanes = q_rope.shape
    rope = k_rope.shape[2]
    with model_scope("mla_project"):
        q_rot = _rope_call(b, s, lanes, q_rope.dtype, rope, shift, False,
                           interpret)(q_rope, *tables)
        k_rep = jnp.tile(k_rope, (1, 1, 128 // rope))
    fwd, _, _ = _latent_calls(
        q_nope, kv, rope, kpm, heads, scale, interpret, tile)
    o, lse = fwd(q_nope, q_rot, kv, k_rep, *_kpm_blocks(kpm, tile[1]))
    return o, (q_nope, q_rot, kv, k_rep, tables, kpm, o, lse)


def _latent_bwd(heads, scale, shift, interpret, tile, res, do):
    q_nope, q_rot, kv, k_rep, (cos, lo, hi), kpm, o, lse = res
    b, s, lanes = q_rot.shape
    rope = lanes // heads
    _, dq_call, dkv_call = _latent_calls(
        q_nope, kv, rope, kpm, heads, scale, interpret, tile)
    blocks = _kpm_blocks(kpm, tile[1])
    dq_nope, dq_wide, delta = dq_call(
        q_nope, q_rot, kv, k_rep, do, lse, o, *blocks)
    dkv, dk_parts = dkv_call(
        q_nope, q_rot, kv, k_rep, do, lse, delta, *blocks)
    with model_scope("mla_project"):
        # the rotation's transpose is the rotation by the negated angles
        dq_rope = _rope_call(b, s, lanes, q_rot.dtype, rope, shift, True,
                             interpret)(dq_wide, cos, -lo, -hi)
        # the shared key's gradient: the heads' parts (each in its own lanes
        # of its 128) summed group on group, as one product with 0 / 1
        fold = (jnp.arange(dk_parts.shape[2])[:, None] % rope
                == jnp.arange(rope)[None, :]).astype(dk_parts.dtype)
        dk_rope = jnp.dot(dk_parts, fold, preferred_element_type=jnp.float32)
    # the tables and the int mask take no cotangent (None == symbolic zero)
    return dq_nope, dq_rope, dkv, dk_rope.astype(k_rep.dtype), None, None


_latent.defvjp(_latent_fwd_res, _latent_bwd)


# ---------------------------------------------------------------------------
# Blockwise long-context path (single chip)
# ---------------------------------------------------------------------------

# The Pallas kernels keep K/V (and, in the dk/dv kernel, Q/dO) fully
# VMEM-resident per (batch, head) — the fastest layout while they fit. The
# pipeline double-buffers every input, so the resident pair costs TWICE its
# VMEM size out of the 16 MiB scoped limit, next to the q/do/o blocks and
# fp32 accumulators; and VMEM pads the head dim to 128 lanes, so d=64 costs
# what d=128 does (_kv_vmem_bytes). Past the budget, attention switches to
# the blockwise-XLA path below.
#
# The budget is the edge of a compile sweep against the v5e (libtpu 0.0.34,
# compile-only topology; fwd and fwd+bwd of causal, key-padding, GQA+window
# and decode-shaped calls, bf16 and f32, d=64 and d=128, batch*heads 4..64,
# K+V in 0.25 MiB steps): everything up to 7.5 MiB of padded K+V compiles;
# 7.75 MiB is refused for VMEM in the dk/dv kernel (f32, batch*heads 64) and
# 8 MiB in every kernel. 7 MiB keeps half a MiB of margin under the edge.
_KV_RESIDENT_BYTES = 7 * 1024 * 1024
# XLA fallback budget: the reference implementation materializes the full
# (b, h, sq, sk) fp32 score tensor; beyond this it pages through HBM or
# OOMs, so the blockwise path takes over.
_SCORE_BYTES = 1 << 30
# what a tile of the kernels may count on (_flash_tiles): the scoped VMEM
# limit, and the room each tile needs beside the double-buffered resident
# pair for its scores, statistics and blocks. From a compile sweep against
# the v5e like the one above (forward + backward of causal, key-padding and
# GQA+window+key-padding calls, bf16 and f32, d=64 and d=128, batch*heads
# up to 64, the resident pair in 0.5 MiB steps): 1024 x 1024 compiles with
# 7 MiB of room, 512 x 512 with 4, 256 x 256 with 2 (the whole edge); each
# keeps a MiB or two of margin.
_VMEM_SCOPED_BYTES = 16 * 1024 * 1024
_TILE_ROOM = (
    (1024, 9 * 1024 * 1024),
    (512, 6 * 1024 * 1024),
    (256, 3 * 1024 * 1024),
)


def _kv_vmem_bytes(seq: int, d: int, esize: int, d_v: int = None) -> int:
    """VMEM footprint of one (batch, head)'s resident pair (K+V, or Q+dO:
    one member ``d`` wide, the other ``d_v``, which defaults to ``d``):
    each head dim is padded to the 128-lane tile."""
    lanes = lambda w: -(-w // 128) * 128
    return seq * (lanes(d) + lanes(d if d_v is None else d_v)) * esize


def _flash_tiles(sq: int, sk: int, window, resident: int,
                 block_q=None, block_k=None):
    """The three kernels' (bq, bk), or None when no tile divides its
    sequence (the call then goes to XLA, as it did at 128 x 128).

    ``block_q`` / ``block_k`` given: that tile, capped by the sequence.
    Derived, from what the call can observe: a kernel is as fast as the
    STEP of its loop is wide (the kv step in forward and dq, the q step in
    dk/dv), and on the chip no kernel wanted another pair than the others
    by more than 4% (PERF.md 6, PR 26), so both sides take the largest of
    1024, 512, 256, 128 that divides their sequence, falling to
    ``min(128, s)``; a sliding window keeps the tiles under half of it (a
    tile reads window + tile keys whatever it needs); and the tiles shrink
    back to 128 as the resident pair's ``resident`` bytes (twice over: the
    pipeline double-buffers it) leave less of the scoped VMEM for the
    tile's scores (``_TILE_ROOM``), so the residency edge stays where it
    was. Sequences under 128 keep the tiles they had."""
    def fit(s, cap):
        for t in (1024, 512, 256, 128):
            if t <= cap and s % t == 0:
                return t
        return min(128, s)

    if block_q is not None or block_k is not None:
        bq = min(128 if block_q is None else block_q, sq)
        bk = min(128 if block_k is None else block_k, sk)
    else:
        cap = 128
        if min(sq, sk) >= 128:
            room = _VMEM_SCOPED_BYTES - 2 * resident
            cap = next((t for t, need in _TILE_ROOM if room >= need), 128)
            if window is not None:
                cap = min(cap, max(128, window // 2))
        bq, bk = fit(sq, cap), fit(sk, cap)
    return None if sq % bq or sk % bk else (bq, bk)


def _bw_chunk(n: int, target: int) -> int:
    c = min(target, n)
    while n % c:
        c -= 1
    return c


def _blockwise_masks(i, j, cq, ck, offs, causal, window):
    """(cq, ck) keep-mask or None — the kernels' band mask at chunk
    granularity with the bottom-right offset (window implies causal at the
    API layer, so non-causal chunks are unmasked)."""
    if not causal:
        return None
    return _causal_keep(i, j, cq, ck, window, offs)


def _blockwise_kv_bounds(i, cq, ck, nk, offs, causal, window):
    """[lo, hi) kv-chunk range intersecting q chunk ``i``'s band."""
    hi = _causal_hi(i, cq, ck, nk, offs) if causal else nk
    lo = _window_lo(i, cq, ck, window, offs) if window is not None else 0
    return lo, hi


def _bw_score(qi, kc, scale):
    # operands keep the input dtype, fp32 accumulation (same MXU policy as
    # the Pallas kernels)
    return (
        jnp.einsum(
            "bGgqd,bGkd->bGgqk", qi, kc, preferred_element_type=jnp.float32
        )
        * scale
    )


def _kpm_chunk_keep(kpm, j, ck):
    """(b, 1, 1, 1, ck) keep-mask slice of the key-padding mask."""
    sl = jax.lax.dynamic_slice_in_dim(kpm, j * ck, ck, axis=1)
    return (sl == 0)[:, None, None, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _blockwise(q5, kv, kpm, scale, causal, window, cq, ck):
    o, _ = _blockwise_fwd_res(q5, kv, kpm, scale, causal, window, cq, ck)
    return o


def _blockwise_fwd_res(q5, kv, kpm, scale, causal, window, cq, ck):
    """q5: (b, h_kv, g, sq, d); k/v: (b, h_kv, sk, d). Outer scan over q
    chunks, inner fori over the kv chunks in the band — memory is one
    (cq, ck) score tile per (b, h) instead of (sq, sk)."""
    k, v = kv
    b, h_kv, g, sq, _ = q5.shape
    sk, d = k.shape[2], v.shape[3]  # the output is as wide as v
    nq, nk = sq // cq, sk // ck
    offs = sk - sq
    has_kpm = kpm is not None

    def q_chunk_step(_, i):
        qi = jax.lax.dynamic_slice_in_dim(q5, i * cq, cq, axis=3)
        lo, hi = _blockwise_kv_bounds(i, cq, ck, nk, offs, causal, window)

        def kv_step(j, state):
            acc, m, l = state
            kc = jax.lax.dynamic_slice_in_dim(k, j * ck, ck, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(v, j * ck, ck, axis=2)
            s = _bw_score(qi, kc, scale)
            keep = _blockwise_masks(i, j, cq, ck, offs, causal, window)
            if keep is not None:
                s = jnp.where(keep, s, _NEG_INF)
            if has_kpm:
                s = jnp.where(_kpm_chunk_keep(kpm, j, ck), s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bGgqk,bGkd->bGgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            )
            return acc_new, m_new, l_new

        init = (
            jnp.zeros((b, h_kv, g, cq, d), jnp.float32),
            jnp.full((b, h_kv, g, cq), _NEG_INF, jnp.float32),
            jnp.zeros((b, h_kv, g, cq), jnp.float32),
        )
        acc, m, l = jax.lax.fori_loop(lo, hi, kv_step, init)
        # fully-masked rows -> zeros + lse sentinel (same contract as the
        # Pallas kernel, see _flash_fwd_kernel)
        dead = m <= _NEG_INF * 0.5
        l = jnp.maximum(l, 1e-30)
        o_i = jnp.where(dead[..., None], 0.0, acc / l[..., None])
        lse_i = jnp.where(dead, -_NEG_INF, m + jnp.log(l))
        return None, (o_i.astype(q5.dtype), lse_i)

    _, (o_chunks, lse_chunks) = jax.lax.scan(
        q_chunk_step, None, jnp.arange(nq)
    )
    # (nq, b, G, g, cq, ...) -> (b, G, g, sq, ...)
    o = jnp.moveaxis(o_chunks, 0, 3).reshape(b, h_kv, g, sq, d)
    lse = jnp.moveaxis(lse_chunks, 0, 3).reshape(b, h_kv, g, sq)
    return o, (q5, kv, kpm, o, lse)


def _blockwise_bwd(scale, causal, window, cq, ck, res, do):
    q5, (k, v), kpm, o, lse = res
    b, h_kv, g, sq, d = q5.shape
    sk = k.shape[2]
    nq, nk = sq // cq, sk // ck
    offs = sk - sq
    has_kpm = kpm is not None
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # (b, G, g, sq)

    def recompute_p(qi, kc, i, j):
        s = _bw_score(qi, kc, scale)
        keep = _blockwise_masks(i, j, cq, ck, offs, causal, window)
        lse_i = jax.lax.dynamic_slice_in_dim(lse, i * cq, cq, axis=3)
        p = jnp.exp(s - lse_i[..., None])
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        if has_kpm:
            p = jnp.where(_kpm_chunk_keep(kpm, j, ck), p, 0.0)
        return p

    # dq: per q chunk, accumulate over its kv band
    def dq_step(_, i):
        qi = jax.lax.dynamic_slice_in_dim(q5, i * cq, cq, axis=3)
        doi = jax.lax.dynamic_slice_in_dim(do, i * cq, cq, axis=3)
        di = jax.lax.dynamic_slice_in_dim(delta, i * cq, cq, axis=3)
        lo, hi = _blockwise_kv_bounds(i, cq, ck, nk, offs, causal, window)

        def kv_step(j, dq_i):
            kc = jax.lax.dynamic_slice_in_dim(k, j * ck, ck, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(v, j * ck, ck, axis=2)
            p = recompute_p(qi, kc, i, j)
            dp = jnp.einsum(
                "bGgqd,bGkd->bGgqk", doi, vc, preferred_element_type=jnp.float32
            )
            ds = p * (dp - di[..., None]) * scale
            return dq_i + jnp.einsum(
                "bGgqk,bGkd->bGgqd", ds.astype(kc.dtype), kc,
                preferred_element_type=jnp.float32,
            )

        dq_i = jax.lax.fori_loop(
            lo, hi, kv_step, jnp.zeros((b, h_kv, g, cq, d), jnp.float32)
        )
        return None, dq_i

    _, dq_chunks = jax.lax.scan(dq_step, None, jnp.arange(nq))
    dq = jnp.moveaxis(dq_chunks, 0, 3).reshape(b, h_kv, g, sq, d)

    # dk/dv: per kv chunk, accumulate over the q band (group summed)
    def dkv_step(_, j):
        kc = jax.lax.dynamic_slice_in_dim(k, j * ck, ck, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(v, j * ck, ck, axis=2)
        lo, hi = _q_band(j, cq, ck, nq, causal, window, offs)

        def q_step(i, carry):
            dk_j, dv_j = carry
            qi = jax.lax.dynamic_slice_in_dim(q5, i * cq, cq, axis=3)
            doi = jax.lax.dynamic_slice_in_dim(do, i * cq, cq, axis=3)
            di = jax.lax.dynamic_slice_in_dim(delta, i * cq, cq, axis=3)
            p = recompute_p(qi, kc, i, j)
            dv_j = dv_j + jnp.einsum(
                "bGgqk,bGgqd->bGkd", p.astype(doi.dtype), doi,
                preferred_element_type=jnp.float32,
            )
            dp = jnp.einsum(
                "bGgqd,bGkd->bGgqk", doi, vc, preferred_element_type=jnp.float32
            )
            ds = p * (dp - di[..., None]) * scale
            dk_j = dk_j + jnp.einsum(
                "bGgqk,bGgqd->bGkd", ds.astype(qi.dtype), qi,
                preferred_element_type=jnp.float32,
            )
            return dk_j, dv_j

        init = (
            jnp.zeros((b, h_kv, ck, d), jnp.float32),
            jnp.zeros((b, h_kv, ck, v.shape[3]), jnp.float32),
        )
        dk_j, dv_j = jax.lax.fori_loop(lo, hi, q_step, init)
        return None, (dk_j, dv_j)

    _, (dk_chunks, dv_chunks) = jax.lax.scan(dkv_step, None, jnp.arange(nk))
    dk = jnp.moveaxis(dk_chunks, 0, 2).reshape(b, h_kv, sk, d).astype(k.dtype)
    dv = jnp.moveaxis(dv_chunks, 0, 2).reshape(v.shape).astype(v.dtype)
    return dq.astype(q5.dtype), (dk, dv), None


_blockwise.defvjp(_blockwise_fwd_res, _blockwise_bwd)


def _attn_blockwise(q, k, v, scale, causal, window, kpm, chunk_q, chunk_k):
    """Long-context attention by (cq, ck) tiles: O(sq·d) state + one score
    tile live at a time. GQA-grouped, key-padding aware, rectangular-causal
    (bottom-right) like the rest of this module.

    Non-multiple sequence lengths are FRONT-padded up to the target chunk
    instead of shrinking the chunk toward a divisor (a prime 16k+1 length
    would otherwise degrade to chunk 1 and run thousands of tiny tiles).
    Front padding preserves the bottom-right causal/window alignment for
    any pad amounts: real row i maps to i+pq, real key j to j+pk, and the
    band bound j' <= i' + (sk'-sq') reduces exactly to j <= i + (sk-sq);
    padded keys are masked through the key-padding path and padded query
    rows are sliced off the output (their grads vanish through the same
    pad/slice AD)."""
    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    group = h // h_kv
    cq_t = max(1, min(chunk_q, sq))
    ck_t = max(1, min(chunk_k, sk))
    pq = (-sq) % cq_t
    pk = (-sk) % ck_t
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (pk, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (pk, 0), (0, 0)))
        base = kpm if kpm is not None else jnp.zeros((b, sk), bool)
        kpm = jnp.concatenate([jnp.ones((b, pk), bool), base], axis=1)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (pq, 0), (0, 0)))
    sq_p, sk_p = sq + pq, sk + pk
    cq = _bw_chunk(sq_p, cq_t)  # sq_p % cq_t == 0, so this is cq_t
    ck = _bw_chunk(sk_p, ck_t)
    q5 = q.reshape(b, h_kv, group, sq_p, d)
    o = _blockwise(q5, (k, v), kpm, scale, causal, window, cq, ck)
    o = o.reshape(b, h, sq_p, v.shape[3])
    return o[:, :, pq:, :] if pq else o


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    scale: float = None,
    mask=None,
    key_padding_mask=None,
    window: int = None,
    impl: str = "auto",
    block_q: int = None,
    block_k: int = None,
):
    """Multi-head attention; q,k: (batch, heads, seq, d_qk), v: (batch,
    heads, seq, d_v). The output is ``d_v`` wide; ``d_v`` may differ from
    ``d_qk`` (latent attention's 192 / 128) on every path.

    ``key_padding_mask`` ((b, sk) bool, True = padded-out key) stays on the
    Pallas fast path — the reference fmha's variable-seqlen capability
    (contrib/fmha: cu_seqlens) expressed as a mask. An arbitrary ``mask``
    (True = masked out, broadcastable to (b, h, sq, sk)) forces the XLA
    path; the Pallas kernel covers the unmasked / causal / key-padded fast
    paths that the reference's fmha/fast_multihead_attn accelerate.

    ``window`` (sliding-window attention, mistral-style; requires
    ``causal=True``): each query attends only to the last ``window`` keys.
    The kernels skip kv/q blocks fully outside the band, so compute scales
    O(seq * window) instead of O(seq^2).

    GQA: k/v may carry ``h_kv`` heads with ``h % h_kv == 0`` — query head
    ``g * (h // h_kv) + j`` attends through kv head ``g`` (consecutive
    grouping, the llama convention). The kernels index K/V by
    ``q_head // group`` so no materialized head broadcast is needed.

    ``block_q`` / ``block_k`` force one (q, kv) tile on all three kernels
    (and 8x that as the blockwise path's chunk); left ``None`` each kernel's
    tile follows the call's shape (``_flash_tiles``).
    """
    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    if h % h_kv != 0:
        raise ValueError(f"q heads ({h}) not a multiple of kv heads ({h_kv})")
    group = h // h_kv
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (mistral semantics)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    kpm_i = (
        None
        if key_padding_mask is None
        else key_padding_mask.astype(jnp.int32)  # (b, sk), 1 = padded
    )
    # the blockwise path's chunk: 8 tiles of the kernels' smallest
    chunk_q = 8 * (128 if block_q is None else block_q)
    chunk_k = 8 * (128 if block_k is None else block_k)
    if impl == "blockwise":
        if mask is not None:
            raise ValueError("blockwise path takes key_padding_mask, not mask")
        return _attn_blockwise(
            q, k, v, scale, causal, window, kpm_i, chunk_q, chunk_k
        )
    use_pallas, interpret = resolve_impl(impl)
    # the backward's dk/dv kernel holds Q/dO resident the way the others
    # hold K/V, so the longer of the two sequences is what must fit
    d_v = v.shape[3]
    resident = _kv_vmem_bytes(
        max(sq, sk), d, jnp.dtype(q.dtype).itemsize, d_v)
    kv_resident = resident <= _KV_RESIDENT_BYTES
    tile = _flash_tiles(sq, sk, window, resident, block_q, block_k)
    pallas_ok = (
        use_pallas
        and mask is None
        and tile is not None
        and (not causal or sq == sk)
        and kv_resident
    )
    # long-context autodispatch: whenever the kernel is out (K/V past the
    # VMEM-residency budget, or any other pallas_ok reason) AND the dense
    # fallback's full fp32 score tensor would blow its budget, tile instead
    if mask is None and not pallas_ok and (
        (use_pallas and not kv_resident)
        or 4 * b * h * sq * sk > _SCORE_BYTES
    ):
        return _attn_blockwise(
            q, k, v, scale, causal, window, kpm_i, chunk_q, chunk_k
        )
    if not pallas_ok:
        if key_padding_mask is not None:
            # _attn_ref's dead-row zeroing covers fully-padded rows
            kp = key_padding_mask[:, None, None, :]  # (b, 1, 1, sk)
            mask = kp if mask is None else jnp.logical_or(mask, kp)
        return _attn_ref(q, k, v, scale, causal, mask, window)
    q3 = q.reshape(b * h, sq, d)
    k3 = k.reshape(b * h_kv, sk, d)
    v3 = v.reshape(b * h_kv, sk, d_v)
    o = _flash(
        q3, (k3, v3), kpm_i, h, group, scale, causal, interpret, tile, window
    )
    return o.reshape(b, h, sq, d_v)


def latent_flash_attention(
    q_nope,
    q_rope,
    kv,
    k_rope,
    freqs,
    *,
    heads: int,
    interleaved: bool = False,
    scale: float = None,
    key_padding_mask=None,
    impl: str = "auto",
    block_q: int = None,
    block_k: int = None,
):
    """Causal multi-head latent attention on what its projections wrote,
    batch-major: ``q_nope`` (b, s, heads * nope) and ``q_rope`` (b, s,
    heads * rope), the no-rope and rope columns of the query projection;
    ``kv`` (b, s, heads * (nope + d_v)), head h's no-rope key and value
    side by side as the kv up-projection writes them; ``k_rope`` (b, s,
    rope), the ONE rope key all heads share. ``q_rope`` and ``k_rope``
    arrive unrotated with ``freqs`` ((s, 1, 1, rope) angles,
    ``rope_frequencies``). Returns the context (b, s, heads * d_v), which
    the output projection reads as it is.

    The same mathematics as ``flash_attention`` on q = [q_nope | rot
    q_rope], k = [k_nope | rot k_rope for every head], v: the score is the
    sum of the two parts' products, fp32. The kernels (``_flash_calls``
    with ``rope``) read a head as a 128-lane column range of these arrays,
    so no (tokens x heads x d) array is sliced, concatenated, broadcast or
    transposed on either side of them, forward or backward; the one such
    array written is q's rotated rope part. That needs whole lane tiles:
    nope and d_v multiples of 128, rope a divisor of 128 with heads a
    multiple of 128 // rope, and the resident operands within the VMEM
    budget. Any other call (and ``impl="xla"``) assembles q, k and v and
    goes through ``flash_attention``."""
    b, s, width = q_nope.shape
    nope, rope = width // heads, k_rope.shape[2]
    d_v = kv.shape[2] // heads - nope
    if scale is None:
        scale = 1.0 / math.sqrt(nope + rope)
    angles = freqs[:s].reshape(1, s, 1, rope)
    rotate = functools.partial(
        apply_rotary_pos_emb, freqs=angles, interleaved=interleaved)
    k_rot = rotate(k_rope[:, :, None, :])  # (b, s, 1, rope): XLA's, it is small
    use_pallas, interpret = resolve_impl(impl)
    resident = _kv_vmem_bytes(s, nope + 128, jnp.dtype(kv.dtype).itemsize, d_v)
    tile = _flash_tiles(s, s, None, resident, block_q, block_k)
    whole_lanes = (
        nope % 128 == 0 and d_v % 128 == 0 and 128 % rope == 0
        and heads % (128 // rope) == 0
    )
    if (use_pallas and whole_lanes and tile is not None
            and resident <= _KV_RESIDENT_BYTES):
        kpm_i = (None if key_padding_mask is None
                 else key_padding_mask.astype(jnp.int32))
        return _latent(
            q_nope, q_rope, kv, k_rot[:, :, 0], _rope_tables(
                angles.reshape(s, rope).astype(jnp.float32), rope,
                interleaved),
            kpm_i, heads, scale, 1 if interleaved else rope // 2, interpret,
            tile)
    heads_of = lambda t: t.reshape(b, s, heads, -1)
    kv4 = heads_of(kv)
    q = jnp.concatenate([heads_of(q_nope), rotate(heads_of(q_rope))], axis=-1)
    k = jnp.concatenate(
        [kv4[..., :nope], jnp.broadcast_to(k_rot, (b, s, heads, rope))],
        axis=-1)
    o = flash_attention(
        *(jnp.swapaxes(t, 1, 2) for t in (q, k, kv4[..., nope:])),
        causal=True, scale=scale, key_padding_mask=key_padding_mask,
        impl=impl, block_q=block_q, block_k=block_k)
    return jnp.swapaxes(o, 1, 2).reshape(b, s, heads * d_v)


# -- decode attention over a paged KV pool ----------------------------------

# keys one step of the paged kernel's loop fetches and attends (whole pool
# blocks: a block with all its heads is one contiguous copy): on the chip
# 256 beat 128 and 512 on ragged lanes (PERF.md 6, PR 34); fewer where the
# two double-buffered chunks would pass their share of VMEM
_PAGED_CHUNK_KEYS = 256
_PAGED_VMEM_BYTES = 4 * 1024 * 1024


def _paged_decode_kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, *, scale, window, bs, chunk,
                         max_blocks, hd, group):
    """One lane: its query rows against the keys its block table names, a
    chunk of ``chunk`` pool blocks at a time, double-buffered. ``q_ref`` is
    (1, rows, h_kv * hd): row i holds query head i in its kv head's lanes
    and zeros beside them, so one product with a (keys, h_kv * hd) chunk
    gives every head's scores; the pools stay in HBM, a block (bs,
    h_kv * hd). The loop runs over the chunks the lane's length (and
    window) covers and no further."""
    lane = pl.program_id(0)
    length = lengths_ref[lane]
    keys = chunk * bs
    lo = (0 if window is None
          else jax.lax.div(jnp.maximum(length - window, 0), keys))
    hi = jax.lax.div(length + keys - 1, keys)

    def copies(i, slot):
        """The DMAs of chunk ``i`` into buffer ``slot``: whole chunks, so
        past the lane's last block they fetch what the (clipped) table
        names there, bytes the length masks."""
        out = []
        for c in range(chunk):
            col = jnp.minimum(i * chunk + c, max_blocks - 1)
            blk = tables_ref[lane * max_blocks + col]
            rows = pl.ds(c * bs, bs)
            out.append(pltpu.make_async_copy(
                k_hbm.at[blk], k_buf.at[slot, rows], sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[blk], v_buf.at[slot, rows], sems.at[1, slot]))
        return out

    @pl.when(lo < hi)
    def _():
        for cp in copies(lo, jax.lax.rem(lo, 2)):
            cp.start()

    q = q_ref[0]
    rows, width = q.shape

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < hi)
        def _():
            for cp in copies(i + 1, 1 - slot):
                cp.start()

        for cp in copies(i, slot):
            cp.wait()
        s = _dot(q, k_buf[slot], _NT) * scale  # (rows, keys), fp32
        at = i * keys + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = at < length
        if window is not None:
            keep = jnp.logical_and(keep, at >= length - window)
        s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        v = v_buf[slot]
        return (m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True),
                acc * alpha + _dot(p.astype(v.dtype), v, _NN))

    m, l, acc = jax.lax.fori_loop(lo, hi, body, (
        jnp.full((rows, 1), _NEG_INF, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32),
        jnp.zeros((rows, width), jnp.float32)))
    # a row that saw no key (length 0) is zero, as the flash kernels' dead rows
    o = jnp.where(m <= _NEG_INF * 0.5, 0.0, acc / jnp.maximum(l, 1e-30))
    # head i's output lies in its kv head's lanes: row j of the result
    # holds, for every kv head, the j-th query head of its group
    row = jax.lax.broadcasted_iota(jnp.int32, o.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, o.shape, 1)
    own = col // hd == row // group
    for j in range(group):
        mine = jnp.logical_and(own, row % group == j)
        o_ref[0, j:j + 1, :] = jnp.sum(
            jnp.where(mine, o, 0.0), axis=0, keepdims=True).astype(o_ref.dtype)


@functools.lru_cache(maxsize=32)
def _paged_decode_call(lanes, rows, group, hd, bs, width, max_blocks, dtype,
                       scale, window, interpret):
    """The ``pallas_call`` of one static configuration (cached: a model
    makes the same call once a layer)."""
    keys = min(_PAGED_CHUNK_KEYS,
               _PAGED_VMEM_BYTES // (4 * width * dtype.itemsize))
    chunk = max(1, min(keys // bs, max_blocks))
    buf = pltpu.VMEM((2, chunk * bs, width), dtype)
    return pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=scale, window=window, bs=bs,
            chunk=chunk, max_blocks=max_blocks, hd=hd, group=group),
        out_shape=jax.ShapeDtypeStruct((lanes, group, width), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes,),
            in_specs=[
                pl.BlockSpec((1, rows, width), lambda b, t, n: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, group, width),
                                   lambda b, t, n: (b, 0, 0)),
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        interpret=interpret,
        name="paged_decode",
        metadata=kernel_metadata("paged_decode", chunk_keys=chunk * bs,
                                 d_qk=hd, d_v=hd),
    )


def _paged_decode_xla(q, k_pool, v_pool, tables, lengths, scale, window):
    """The same attention in plain XLA: a scan over the lanes' table
    columns, each step one pool block a lane (gathered as the table names
    it, so a block named twice counts twice, as in the kernel) folded into
    an online softmax. The largest array is one block a lane: no lane's
    window is ever gathered whole."""
    lanes, heads, hd = q.shape
    bs = k_pool.shape[1]
    h_kv = k_pool.shape[2] // hd
    q4 = q.reshape(lanes, h_kv, heads // h_kv, hd)
    n = lengths[:, None, None, None]

    def block(carry, j):
        m, l, acc = carry
        k, v = (pool[tables[:, j]].reshape(lanes, bs, h_kv, hd)
                for pool in (k_pool, v_pool))
        s = jnp.einsum("lgjd,ltgd->lgjt", q4, k,
                       preferred_element_type=jnp.float32) * scale
        at = j * bs + jnp.arange(bs)
        keep = at < n
        if window is not None:
            keep = jnp.logical_and(keep, at >= n - window)
        s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + jnp.einsum(
                    "lgjt,ltgd->lgjd", p.astype(q.dtype), v,
                    preferred_element_type=jnp.float32)), None

    stat = q4.shape[:3] + (1,)
    (m, l, acc), _ = jax.lax.scan(block, (
        jnp.full(stat, _NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32),
        jnp.zeros(q4.shape, jnp.float32)), jnp.arange(tables.shape[1]))
    # a lane that saw no key (length 0) is zero, as in the kernel
    out = jnp.where(m <= _NEG_INF * 0.5, 0.0, acc / jnp.maximum(l, 1e-30))
    return out.astype(q.dtype).reshape(q.shape)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           scale: float, window: int = None,
                           impl: str = "auto"):
    """One decode token a lane against the keys its block table names, read
    from the pool where they lie. ``q``: (lanes, heads, hd); the pools:
    (num_blocks, block_size, h_kv * hd), a block's keys as rows, kv head g
    in lanes [g * hd, (g + 1) * hd), ``heads % h_kv == 0`` (consecutive
    grouping, as ``flash_attention``); ``block_tables``: (lanes,
    max_blocks_per_lane) int32, lane-local block j -> pool block;
    ``lengths``: (lanes,) int32, the keys the lane attends: positions
    [0, length), or with a sliding ``window`` the last ``window`` of them.
    Returns (lanes, heads, hd). Scores, softmax statistics and the P.V sum
    in float32.

    Every table entry is clipped into the pool before it addresses memory,
    so an out-of-range entry (the engine's sentinel ``num_blocks``) inside
    a lane's length reads another block's bytes and never faults; a length
    of 0 gives zeros.

    On a TPU a Pallas kernel (``paged_decode``): table and lengths are
    scalar-prefetch operands, a lane's blocks come by DMA a chunk of
    ``_PAGED_CHUNK_KEYS`` keys at a time, double-buffered, and the loop
    ends at the lane's length, so a lane costs what it holds. It needs
    whole tiles: h_kv * hd a multiple of 128 lanes, block_size of the
    dtype's sublane tile, one dtype. Any other call, ``impl="xla"`` and
    ``auto`` off the TPU compute the same in plain XLA, a block a lane at a
    time (``_paged_decode_xla``): no gather of a lane's window on either
    path."""
    lanes, heads, hd = q.shape
    nb, bs, width = k_pool.shape
    if width % hd or heads % (width // hd):
        raise ValueError(
            f"pool rows {width} wide hold no whole number of {hd}-wide kv "
            f"heads that divides the {heads} query heads")
    h_kv = width // hd
    group = heads // h_kv
    tables = jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    use_pallas, interpret = resolve_impl(impl)
    whole_tiles = (
        width % 128 == 0
        and bs % (32 // jnp.dtype(k_pool.dtype).itemsize) == 0
        and q.dtype == k_pool.dtype == v_pool.dtype
    )
    if not (use_pallas and whole_tiles):
        return _paged_decode_xla(q, k_pool, v_pool, tables, lengths, scale,
                                 window)
    # query head i into the lanes of its kv head, zeros beside them; rows
    # padded to the bf16 sublane tile
    rows = -(-heads // 16) * 16
    in_group = (jnp.arange(heads)[:, None] // group
                == jnp.arange(h_kv)[None, :])
    q_rows = jnp.where(in_group[None, :, :, None], q[:, :, None, :],
                       jnp.zeros((), q.dtype)).reshape(lanes, heads, width)
    q_rows = jnp.pad(q_rows, ((0, 0), (0, rows - heads), (0, 0)))
    out = _paged_decode_call(
        lanes, rows, group, hd, bs, width, tables.shape[1],
        jnp.dtype(q.dtype), float(scale), window, interpret,
    )(tables.reshape(-1), lengths, q_rows, k_pool, v_pool)
    # (lanes, group, h_kv * hd): member j of every kv head's group
    return out.reshape(lanes, group, h_kv, hd).transpose(0, 2, 1, 3).reshape(
        lanes, heads, hd)
