"""GPT-2 in plain ``jax.numpy``: the benchmark's reference and its weights.

A pre-LN decoder (Radford et al. 2019, as Megatron-LM lays it out): token
embedding + positions, ``layers`` blocks of LN -> fused QKV -> causal softmax
attention -> projection -> residual, LN -> h->4h -> tanh-GELU -> 4h->h ->
residual, a final LN and the tied output head. Float32 everywhere, matrix
products at ``highest`` precision (on a TPU a float32 product otherwise runs
in bf16 passes). No kernels, no cache, no batching tricks; nothing here
imports the program under test, and the weights are made here from the seed.

One departure from the published model, the program's and noted in
``perf/configs/gpt2_345m.json``: the fused QKV projection is laid out per
head as [q | k | v] (Megatron).

Weights are a flat dict of arrays stacked over layers, so the blocks run as
one ``lax.scan`` and compile in seconds at any depth.

``precision`` selects the arithmetic of the linear layers: ``"f32"`` is the
reference; ``"fp8"`` (e4m3 operands scaled per tensor to the format's range,
float32 accumulation) is the control a comparison has to reject.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0

#: the leaves stacked over layers (the others exist once)
LAYER_LEAVES = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                "ln2_g", "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


def seed_key(seed):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=(
    "layers", "hidden", "vocab", "max_positions"))
def init_weights(key, *, layers, hidden, vocab, max_positions):
    """GPT-2's initialisation: N(0, 0.02) matrices, residual projections
    scaled by 1/sqrt(2*layers), zero biases, unit norms. One jitted call."""
    h, std = hidden, 0.02
    ks = jax.random.split(key, 6)
    n = lambda k, shape, s=std: s * jax.random.normal(k, shape, jnp.float32)
    res = std / (2.0 * layers) ** 0.5
    return {
        "wte": n(ks[0], (vocab, h)),
        "wpe": n(ks[1], (max_positions, h)),
        "lnf_g": jnp.ones((h,), jnp.float32),
        "lnf_b": jnp.zeros((h,), jnp.float32),
        "ln1_g": jnp.ones((layers, h), jnp.float32),
        "ln1_b": jnp.zeros((layers, h), jnp.float32),
        "qkv_w": n(ks[2], (layers, h, 3 * h)),
        "qkv_b": jnp.zeros((layers, 3 * h), jnp.float32),
        "proj_w": n(ks[3], (layers, h, h), res),
        "proj_b": jnp.zeros((layers, h), jnp.float32),
        "ln2_g": jnp.ones((layers, h), jnp.float32),
        "ln2_b": jnp.zeros((layers, h), jnp.float32),
        "fc_w": n(ks[4], (layers, h, 4 * h)),
        "fc_b": jnp.zeros((layers, 4 * h), jnp.float32),
        "out_w": n(ks[5], (layers, 4 * h, h), res),
        "out_b": jnp.zeros((layers, h), jnp.float32),
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


def _layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _block(x, lw, *, heads, precision):
    b, s, h = x.shape
    d = h // heads
    y = _layer_norm(x, lw["ln1_g"], lw["ln1_b"])
    qkv = _linear(y, lw["qkv_w"], precision) + lw["qkv_b"]
    q, k, v = jnp.split(qkv.reshape(b, s, heads, 3 * d), 3, -1)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k, precision=HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(d))
    future = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]
    scores = jnp.where(future[None, None], -jnp.inf, scores)
    probs = jax.nn.softmax(scores, -1)
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v, precision=HIGHEST)
    x = x + _linear(ctx.reshape(b, s, h), lw["proj_w"], precision) \
        + lw["proj_b"]
    y = _layer_norm(x, lw["ln2_g"], lw["ln2_b"])
    y = jax.nn.gelu(_linear(y, lw["fc_w"], precision) + lw["fc_b"],
                    approximate=True)
    return x + _linear(y, lw["out_w"], precision) + lw["out_b"]


def forward(w, tokens, *, heads, precision):
    """Logits (b, s, vocab) in float32 for tokens (b, s). Each block is
    recomputed in the backward pass, so that a gradient fits."""
    x = jnp.take(w["wte"], tokens, axis=0)
    x = x + w["wpe"][None, : tokens.shape[1]]
    block = jax.checkpoint(
        functools.partial(_block, heads=heads, precision=precision))
    stacked = {k: w[k] for k in LAYER_LEAVES}
    x, _ = jax.lax.scan(lambda c, lw: (block(c, lw), None), x, stacked)
    x = _layer_norm(x, w["lnf_g"], w["lnf_b"])
    return _linear(x, w["wte"].T, precision)


def token_loss(w, tokens, labels, **kw):
    """Sum of next-token cross entropies (the caller divides)."""
    logits = forward(w, tokens, **kw)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def loss_and_grads(w, tokens, labels, *, rows_per_block, **kw):
    """Mean loss over every token of (rows, seq) and its gradient, computed
    ``rows_per_block`` rows at a time so that it fits beside nothing."""
    rows, seq = tokens.shape
    blocks = rows // rows_per_block
    shape = (blocks, rows_per_block, seq)
    vg = jax.value_and_grad(functools.partial(token_loss, **kw))

    def body(carry, xy):
        loss, grads = carry
        l, g = vg(w, *xy)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grads, g)), None

    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, w))
    (loss, grads), _ = jax.lax.scan(
        body, zero, (tokens.reshape(shape), labels.reshape(shape)))
    n = jnp.float32(rows * seq)
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def adamw(w, g, m, v, step, *, lr, weight_decay, b1=0.9, b2=0.999,
          eps=1e-8):
    """Decoupled-weight-decay Adam with bias correction (Loshchilov &
    Hutter 2019); ``step`` counts from 1."""
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: b1 * m + (1.0 - b1) * g, m, g)
    v = tm(lambda v, g: b2 * v + (1.0 - b2) * g * g, v, g)
    w = tm(lambda p, m, v: p - lr * (
        (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * p), w, m, v)
    return w, m, v


def leaf_norms(tree, heads):
    """L2 norm of every leaf; a stacked leaf gives one norm per layer. The
    fused QKV bias is read as its three parts (``qkv_b.q``, ``.k``, ``.v``):
    the key bias cancels in the softmax, so its gradient is nought to
    rounding while its neighbours' are not, and Adam moves it by round-off
    alone (``compare.training`` leaves such leaves out by their gradient)."""
    out = {}
    for name, x in tree.items():
        if name == "qkv_b":
            parts = x.reshape(x.shape[0], heads, 3, -1)
            norms = jnp.sqrt(jnp.sum(jnp.square(parts), axis=(1, 3)))
            for i, part in enumerate("qkv"):
                out[f"qkv_b.{part}"] = norms[:, i]
        else:
            axes = tuple(range(1, x.ndim)) if name in LAYER_LEAVES else None
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


@functools.partial(jax.jit, donate_argnums=(0, 1, 2), static_argnames=(
    "heads", "precision", "rows_per_block", "lr", "weight_decay"))
def _train_step(w, m, v, tokens, labels, step, *, heads, precision,
                rows_per_block, lr, weight_decay):
    loss, g = loss_and_grads(w, tokens, labels, heads=heads,
                             precision=precision,
                             rows_per_block=rows_per_block)
    w, m, v = adamw(w, g, m, v, step, lr=lr, weight_decay=weight_decay)
    return w, m, v, loss, leaf_norms(g, heads)


def train_steps(w0, tokens, labels, *, heads, precision, rows_per_block,
                lr, weight_decay, steps, keep_rows=None):
    """Follow the first ``steps`` AdamW steps from ``w0`` on tokens/labels
    (steps, rows, seq), one jitted step at a time. Returns each step's loss,
    the first gradient's norm per leaf and the norm of each leaf's change
    over the steps. ``keep_rows`` keeps only the first rows of every batch
    and takes the mean over them: the fault a comparison has to notice."""
    if keep_rows is not None:
        tokens, labels = tokens[:, :keep_rows], labels[:, :keep_rows]
    w = jax.tree_util.tree_map(jnp.copy, w0)
    m = jax.tree_util.tree_map(jnp.zeros_like, w0)
    v = jax.tree_util.tree_map(jnp.zeros_like, w0)
    losses, g1 = [], None
    for i in range(steps):
        w, m, v, loss, g = _train_step(
            w, m, v, tokens[i], labels[i], jnp.float32(i + 1), heads=heads,
            precision=precision, rows_per_block=rows_per_block, lr=lr,
            weight_decay=weight_decay)
        g1 = g if i == 0 else g1
        losses.append(loss)
    del m, v
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b), heads))(w, w0)
    return jnp.stack(losses), g1, delta
