"""JoyAI-LLM-Flash (a DeepSeek-V3-family model) in plain ``jax.numpy``: the
benchmark's reference and its weights, for ONE chip's share of the model.

What the model is (``perf/configs/joyai_llm_flash.json`` has the published
keys): pre-RMSNorm decoder blocks with residual adds and no biases, untied
embedding and head, a final RMSNorm.

- Every block's attention is latent attention in its training form:
  ``c_q = norm(x W_dq)``, ``q = c_q W_uq`` -> heads x (nope | rope);
  ``[c_kv | k_r] = x W_dkv``, ``c_kv = norm(c_kv)``, ``[k_nope | v] = c_kv
  W_ukv`` -> heads x (nope | v); rope (interleaved pairs (0,1), (2,3), ...)
  on q's rope part and on ``k_r``, which all heads share; causal softmax of
  ``(q_nope.k_nope + q_rope.k_rope) / sqrt(nope + rope)``; ``o = concat_h(P
  v) W_o``.
- Layer 0 has a dense SwiGLU MLP; every later layer routed experts: ``s =
  sigmoid(x W_r)``, the top-k of ``s + b`` chosen (``b`` takes no
  gradient), gates ``s_chosen / sum(s_chosen) * scale``, ``y = sum_i g_i
  SwiGLU_i(x) + SwiGLU_shared(x)``. No token is dropped.
- One multi-token-prediction block (DeepSeek-V3's): ``h' = [norm(h_i) |
  norm(emb(t_{i+1}))] W_eh`` with ``h_i`` the trunk's last state before the
  final norm, one expert layer with its own norms, a final norm of its own,
  the trunk's head; it predicts ``t_{i+2}``. ``L = CE + lambda * CE_mtp``,
  ``CE_mtp`` the mean over the positions that have a target two ahead.

**The share** (model-configs guide, section 4). This chip is one of the
chips that divide each layer: it holds routed experts ``[first, first +
held)`` of each expert layer and ``vocab`` rows of embedding and head. The
router scores all ``experts`` and chooses among all; what the absent
experts would add is left out, here exactly as in the program, and that
partial sum goes on. ``held = experts, first = 0`` is the uncut layer.

Float32 everywhere, matrix products at ``highest`` precision. No kernels,
no cache, no sorting: every token goes through every held expert and a
one-hot keeps what was routed. Nothing here imports the program under test,
and the weights are made here from the seed. Memory: attention runs a few
heads at a time and the routed experts one at a time, each recomputed in the
backward pass as every block is, so that the gradient of 680M parameters
fits beside Adam's state on one chip.

``precision="fp8"`` (e4m3 operands scaled per tensor, float32 accumulation,
in every linear layer) is the control a comparison has to reject. ``routed=
False`` leaves the routed experts' part out (shared expert only) and
``mtp=False`` the second term out of the loss: the planted faults.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0

#: leaves every block has (trunk layers, then the MTP block's layer)
ATTN_LEAVES = ("ln1", "q_a", "q_a_ln", "q_b", "kv_a", "kv_a_ln", "kv_b",
               "o", "ln2")
#: leaves of an expert layer (trunk layers >= 1, then the MTP block's)
MOE_LEAVES = ("router", "router_b", "shared_in", "shared_out", "w_in",
              "w_out")
STACKED = ATTN_LEAVES + MOE_LEAVES


def seed_key(seed):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def dims_of(config):
    """The sizes this file's functions take, from the configuration file
    (published keys; the share under its ``reduced`` keys)."""
    return dict(
        layers=config["layers_kept"], hidden=config["hidden_size"],
        heads=config["num_attention_heads"],
        vocab=config["assumed"]["padded_vocab_size"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], dense_ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        experts=config["published"]["n_routed_experts"],
        held=config["n_routed_experts"],
        shared=config["n_shared_experts"],
        dense_layers=config["first_k_dense_replace"])


@functools.partial(jax.jit, static_argnames=(
    "layers", "hidden", "heads", "vocab", "q_rank", "kv_rank", "nope",
    "rope", "v_dim", "dense_ffn", "expert_ffn", "experts", "held", "shared",
    "dense_layers", "bias_std"))
def init_weights(key, *, layers, hidden, heads, vocab, q_rank, kv_rank, nope,
                 rope, v_dim, dense_ffn, expert_ffn, experts, held, shared,
                 dense_layers, bias_std=0.05):
    """N(0, 0.02) matrices, unit norms, and a router bias N(0, bias_std)
    that stays fixed: large enough that the top-k of ``s + b`` is not the
    top-k of ``s``. Attention leaves are stacked over ``layers + 1`` blocks
    (the last is the MTP block's), expert leaves over ``layers -
    dense_layers + 1``. One jitted call."""
    assert dense_layers == 1, "one leading dense layer"
    h, a, m = hidden, layers + 1, layers - dense_layers + 1
    ks = iter(jax.random.split(key, 20))
    n = lambda *shape: 0.02 * jax.random.normal(next(ks), shape, jnp.float32)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    return {
        "emb": n(vocab, h), "head": n(h, vocab), "lnf": ones(h),
        "ln1": ones(a, h), "ln2": ones(a, h),
        "q_a": n(a, h, q_rank), "q_a_ln": ones(a, q_rank),
        "q_b": n(a, q_rank, heads * (nope + rope)),
        "kv_a": n(a, h, kv_rank + rope), "kv_a_ln": ones(a, kv_rank),
        "kv_b": n(a, kv_rank, heads * (nope + v_dim)),
        "o": n(a, heads * v_dim, h),
        "mlp_in": n(h, 2 * dense_ffn), "mlp_out": n(dense_ffn, h),
        "router": n(m, h, experts),
        "router_b": bias_std * jax.random.normal(
            next(ks), (m, experts), jnp.float32),
        "shared_in": n(m, h, 2 * expert_ffn * shared),
        "shared_out": n(m, expert_ffn * shared, h),
        "w_in": n(m, held, h, 2 * expert_ffn),
        "w_out": n(m, held, expert_ffn, h),
        "mtp_hnorm": ones(h), "mtp_enorm": ones(h), "mtp_eh": n(2 * h, h),
        "mtp_lnf": ones(h),
    }


def _quant_e4m3(x):
    """Round to fp8 e4m3 after scaling the tensor's largest magnitude to the
    format's; gradients pass straight through."""
    scale = _E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _linear(x, w, precision):
    if precision == "fp8":
        x, w = _quant_e4m3(x), _quant_e4m3(w)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _swiglu(x, w_in, w_out, precision):
    gate, up = jnp.split(_linear(x, w_in, precision), 2, axis=-1)
    return _linear(jax.nn.silu(gate) * up, w_out, precision)


def _rope(x, theta):
    """Rotate consecutive pairs (2i, 2i+1) of the last axis by position *
    theta**(-2i/d); x: (seq, ..., d)."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _attention(x, lw, *, heads, nope, rope, v_dim, theta, eps, precision,
               head_block=1):
    """Latent attention over one row; x: (seq, hidden)."""
    s = x.shape[0]
    c_q = _rms_norm(_linear(x, lw["q_a"], precision), lw["q_a_ln"], eps)
    q = _linear(c_q, lw["q_b"], precision).reshape(s, heads, nope + rope)
    kv_a = _linear(x, lw["kv_a"], precision)
    rank = lw["kv_a_ln"].shape[0]
    c_kv = _rms_norm(kv_a[:, :rank], lw["kv_a_ln"], eps)
    k_rope = _rope(kv_a[:, rank:], theta)                       # (s, rope)
    kv = _linear(c_kv, lw["kv_b"], precision).reshape(s, heads, nope + v_dim)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :],
                                          (s, heads, rope))], -1)
    v = kv[..., nope:]
    future = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]

    @jax.checkpoint
    def some_heads(qkv):
        qh, kh, vh = qkv                                        # (s, hb, d)
        scores = jnp.einsum("qnd,knd->nqk", qh, kh, precision=HIGHEST)
        scores = scores / jnp.sqrt(jnp.float32(nope + rope))
        probs = jax.nn.softmax(jnp.where(future[None], -jnp.inf, scores), -1)
        return jnp.einsum("nqk,knd->qnd", probs, vh, precision=HIGHEST)

    hb = head_block if heads % head_block == 0 else heads
    split = lambda t: jnp.moveaxis(
        t.reshape(s, heads // hb, hb, t.shape[-1]), 1, 0)
    ctx = jax.lax.map(some_heads, (split(q), split(k), split(v)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(s, heads * v_dim)
    return _linear(ctx, lw["o"], precision)


def _experts(x, lw, *, first, top_k, scale, precision, routed):
    """Routed experts' part (for the experts held) plus the shared expert;
    x: (tokens, hidden). Returns (y, chosen (tokens, top_k))."""
    held = lw["w_in"].shape[0]
    s = jax.nn.sigmoid(jnp.matmul(x, lw["router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(lw["router_b"]),
                              top_k)
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20) * scale
    y = _swiglu(x, lw["shared_in"], lw["shared_out"], precision)
    if not routed:
        return y, chosen
    # gate of expert e for each token: its chosen gate, or nought
    weight = jnp.sum(
        jnp.where(chosen[..., None] == first + jnp.arange(held), gates[
            ..., None], 0.0), axis=1)                          # (tokens, held)

    # one expert at a time, recomputed in the backward pass: what sixteen
    # experts keep for it would not fit beside the weights and Adam's state
    expert = jax.checkpoint(lambda x, w_in, w_out, g: g[:, None] * _swiglu(
        x, w_in, w_out, precision))

    def one(acc, ew):
        return acc + expert(x, *ew), None

    y, _ = jax.lax.scan(one, y, (lw["w_in"], lw["w_out"], weight.T))
    return y, chosen


def _block(x, lw, mw, *, attn_kw, moe_kw, eps):
    """One decoder block over one row: attention with the leaves ``lw``,
    then the dense MLP (``mw`` has ``mlp_in``) or the experts. Returns the
    row and the experts chosen (None for a dense block)."""
    x = x + _attention(_rms_norm(x, lw["ln1"], eps), lw, eps=eps, **attn_kw)
    y = _rms_norm(x, lw["ln2"], eps)
    if "mlp_in" in mw:
        return x + _swiglu(y, mw["mlp_in"], mw["mlp_out"],
                           attn_kw["precision"]), None
    out, chosen = _experts(y, mw, **moe_kw)
    return x + out, chosen


def batch_losses(w, tokens, labels, *, heads, nope, rope, v_dim, first,
                 top_k, theta, eps, scale, precision, routed=True):
    """The summed next-token loss of every row, the summed MTP loss (over
    the positions with a target two ahead), and the experts chosen in each
    expert layer (rows, expert layers, seq, top_k); tokens, labels: (rows,
    seq). ``w``'s stacked leaves are lists, one entry a layer. Every block
    takes the rows one after another and is recomputed in the backward
    pass, so that one row's activations are alive at a time."""
    attn_kw = dict(heads=heads, nope=nope, rope=rope, v_dim=v_dim,
                   theta=theta, precision=precision)
    moe_kw = dict(first=first, top_k=top_k, scale=scale,
                  precision=precision, routed=routed)
    layer = lambda i: {k: w[k][i] for k in ATTN_LEAVES}
    moe = lambda i: {k: w[k][i] for k in MOE_LEAVES}
    n_attn, n_moe = len(w["ln1"]), len(w["router"])
    norm = functools.partial(_rms_norm, eps=eps)

    def block(x, lw, mw):
        one = jax.checkpoint(functools.partial(
            _block, attn_kw=attn_kw, moe_kw=moe_kw, eps=eps))
        return jax.lax.map(lambda row: one(row, lw, mw), x)

    def ce(hidden, g, targets):
        """Summed cross entropy of normed states (rows, n, h)."""
        @jax.checkpoint
        def row(ht):
            logits = _linear(norm(ht[0], g), w["head"], precision)
            picked = jnp.take_along_axis(logits, ht[1][:, None], -1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

        return jnp.sum(jax.lax.map(row, (hidden, targets)))

    x = jnp.take(w["emb"], tokens, axis=0)
    x, _ = block(x, layer(0), {k: w[k] for k in ("mlp_in", "mlp_out")})
    chosen = []
    for i in range(1, n_attn - 1):
        x, c = block(x, layer(i), moe(i - 1))
        chosen.append(c)
    main = ce(x, w["lnf"], labels)
    # MTP: position i joins the trunk's state with token i+1 (= labels[i])
    # and predicts token i+2 (= labels[i+1]); the last position has none
    joined = jnp.concatenate(
        [norm(x[:, :-1], w["mtp_hnorm"]),
         norm(jnp.take(w["emb"], labels[:, :-1], axis=0), w["mtp_enorm"])],
        axis=-1)
    y, c = block(_linear(joined, w["mtp_eh"], precision), layer(n_attn - 1),
                 moe(n_moe - 1))
    chosen.append(jnp.pad(c, ((0, 0), (0, 1), (0, 0)), constant_values=-1))
    second = ce(y, w["mtp_lnf"], labels[:, 1:])
    return main, second, jnp.stack(chosen, axis=1)


def loss_and_grads(w, tokens, labels, *, mtp_coeff, mtp=True, **kw):
    """Mean loss ``CE + mtp_coeff * CE_mtp`` over (rows, seq) and its
    gradient. Returns
    ((total, main, mtp), chosen (rows, expert layers, seq, top_k), grads)."""
    rows, seq = tokens.shape
    # differentiate by layer, not by stack: the gradient of a slice of a
    # stacked leaf is a whole stack of zeros around it, one a layer
    unstack = lambda t: {k: list(x) if k in STACKED else x
                         for k, x in t.items()}

    def total(w):
        main, second, chosen = batch_losses(w, tokens, labels, **kw)
        main = main / (rows * seq)
        second = second / (rows * (seq - 1))
        both = main + (mtp_coeff if mtp else 0.0) * second
        return both, (jnp.stack([both, main, second]), chosen)

    (_, (losses, chosen)), grads = jax.value_and_grad(
        total, has_aux=True)(unstack(w))
    return losses, chosen, {k: jnp.stack(g) if k in STACKED else g
                            for k, g in grads.items()}


def bias_step(chosen, experts, speed):
    """Balancing without an auxiliary loss (Wang et al., arXiv 2408.15664;
    DeepSeek-V3's ``noaux_tc`` bias): after a step an expert layer's bias
    goes up by ``speed`` for each expert that took fewer assignments than
    the mean over the batch, down for each that took more. ``chosen``:
    (rows, expert layers, seq, top_k), -1 where a position has none.
    Returns (expert layers, experts)."""
    counts = jnp.sum(chosen[..., None] == jnp.arange(experts),
                     axis=(0, 2, 3), dtype=jnp.float32)
    return speed * jnp.sign(jnp.mean(counts, -1, keepdims=True) - counts)


def adamw(w, g, m, v, step, *, lr, weight_decay, b1=0.9, b2=0.999,
          eps=1e-8):
    """Decoupled-weight-decay Adam with bias correction (Loshchilov &
    Hutter 2019); ``step`` counts from 1. The router's bias is no trained
    weight and stays where it was (``bias_step`` is what moves it)."""
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: b1 * m + (1.0 - b1) * g, m, g)
    v = tm(lambda v, g: b2 * v + (1.0 - b2) * g * g, v, g)
    new = tm(lambda p, m, v: p - lr * (
        (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * p), w, m, v)
    new["router_b"] = w["router_b"]
    return new, m, v


def leaf_norms(tree):
    """L2 norm of every leaf; a stacked leaf gives one norm per layer."""
    return {name: jnp.sqrt(jnp.sum(
        jnp.square(x), axis=tuple(range(1, x.ndim))
        if name in STACKED else None)) for name, x in tree.items()}


_STATIC = ("mtp_coeff", "heads", "nope", "rope", "v_dim", "first", "top_k",
           "theta", "eps", "scale", "precision", "routed", "mtp")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _loss_grads(w, tokens, labels, **kw):
    losses, chosen, g = loss_and_grads(w, tokens, labels, **kw)
    return losses, chosen, g, leaf_norms(g)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("lr", "weight_decay", "bias_speed"))
def _adam_step(w, g, m, v, step, chosen, *, lr, weight_decay, bias_speed):
    w, m, v = adamw(w, g, m, v, step, lr=lr, weight_decay=weight_decay)
    w["router_b"] = w["router_b"] + bias_step(
        chosen, w["router_b"].shape[-1], bias_speed)
    return w, m, v


def train_steps(make_w0, tokens, labels, *, steps, lr, weight_decay,
                bias_speed=0.0, keep_rows=None, **kw):
    """Follow the first ``steps`` AdamW steps from ``make_w0()`` on
    tokens/labels (steps, rows, seq), the router's bias moving by
    ``bias_step`` at ``bias_speed`` after each. Returns each step's (total, main,
    mtp) losses, the first gradient's norm per leaf, the norm of each
    leaf's change over the steps, and the experts the first step chose.

    Memory: weights, gradient and the backward pass's activations are on
    the chip together; Adam's two moments (5.4 GB at the cell's size) wait
    on the host while a gradient is computed and come back for the update,
    and ``make_w0`` is called a second time at the end, so that the start
    never sits beside them. ``keep_rows`` keeps only the first rows of
    every batch and takes the mean over them: the fault a comparison has
    to notice."""
    if keep_rows is not None:
        tokens, labels = tokens[:, :keep_rows], labels[:, :keep_rows]
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    w, moments = make_w0(), None
    losses, g1, chosen1 = [], None, None
    for i in range(steps):
        loss, chosen, g, norms = _loss_grads(w, tokens[i], labels[i], **kw)
        if i == 0:
            g1, chosen1 = norms, chosen
        losses.append(loss)
        m, v = (zeros(w), zeros(w)) if moments is None else jax.device_put(
            moments)
        w, m, v = _adam_step(w, g, m, v, jnp.float32(i + 1), chosen, lr=lr,
                             weight_decay=weight_decay, bias_speed=bias_speed)
        moments = jax.device_get((m, v)) if i + 1 < steps else None
        del m, v, g
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(w, make_w0())
    return jnp.stack(losses), g1, delta, chosen1
