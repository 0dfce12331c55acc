"""Micro-benchmarks for apex_tpu's fused engines.

What it measures (each as median-of-5 timed blocks after a warmup compile):

1. ``adam``: one full optimizer step of ``fused_adam`` over a synthetic
   transformer-shaped param tree — ``fuse="tree"`` (per-leaf tree_map, XLA
   fusion) vs ``fuse="flat"`` (single padded fp32 buffer through
   ``_fused_kernels.adam_flat``).  This answers the question the reference
   answers with amp_C.multi_tensor_adam (csrc/multi_tensor_adam.cu): does a
   single flat kernel beat many small per-tensor updates?
2. ``l2norm``: global grad norm, tree-based ``multi_tensor_l2norm`` vs
   ``l2norm_flat`` over the flattened buffer.
3. ``layer_norm``: ``ops.layer_norm`` Pallas kernel vs the jnp/XLA path.
4. ``attention``: ``ops.attention`` flash kernel vs the jnp/XLA path.

On a TPU backend the Pallas variants run compiled (Mosaic); on CPU, "auto"
dispatch resolves every variant to XLA, so the adam/l2norm rows still give a
real flat-vs-tree comparison while the layer_norm/attention rows collapse to
XLA-vs-XLA (reported as such).  Every record names its platform; a CPU
record is a CPU time, never a device number.

Timing methodology: every number is a chained-iteration SLOPE
(``apex_tpu.utils.benchmarking``), not a per-call wall clock: K
data-dependent iterations run inside one jitted ``lax.scan``;
t(K2)-t(K1) over K2-K1 cancels every per-call constant.

Usage:  python benchmarks/bench_optimizers.py [--cpu] [--params N] [--json]
(``--cpu`` forces the CPU backend through the jax config.)
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


from apex_tpu.utils.benchmarking import (  # noqa: E402
    chained_seconds_per_iter,
    full_reduce as _scalar,
)


def make_param_tree(total_params, key):
    """Transformer-shaped tree: a few big matmul weights, many small
    vectors/norms — the shape mix that makes per-tensor launches expensive
    in the reference and motivates multi_tensor_apply."""
    hidden = max(128, int((total_params / 60) ** 0.5) // 128 * 128)
    layers = max(1, total_params // (12 * hidden * hidden + 13 * hidden))
    tree = {}
    for i in range(layers):
        k = jax.random.fold_in(key, i)
        tree[f"layer_{i}"] = {
            "attn_qkv": jax.random.normal(k, (hidden, 3 * hidden), jnp.float32) * 0.02,
            "attn_out": jax.random.normal(k, (hidden, hidden), jnp.float32) * 0.02,
            "mlp_in": jax.random.normal(k, (hidden, 4 * hidden), jnp.float32) * 0.02,
            "mlp_out": jax.random.normal(k, (4 * hidden, hidden), jnp.float32) * 0.02,
            "ln1_scale": jnp.ones((hidden,)),
            "ln1_bias": jnp.zeros((hidden,)),
            "ln2_scale": jnp.ones((hidden,)),
            "ln2_bias": jnp.zeros((hidden,)),
            "qkv_bias": jnp.zeros((3 * hidden,)),
            "out_bias": jnp.zeros((hidden,)),
            "mlp_in_bias": jnp.zeros((4 * hidden,)),
            "mlp_out_bias": jnp.zeros((hidden,)),
        }
    return tree


def bench_adam(tree, grads, deadline=None):
    import optax

    from apex_tpu.optimizers import fused_adam

    results = {}
    for mode in ("tree", "flat"):
        opt = fused_adam(lr=1e-3, weight_decay=0.01, fuse=mode)
        state = jax.jit(opt.init)(tree)

        def build(k, opt=opt):
            def run(g, s, p):
                def body(carry, _):
                    p, s = carry
                    upd, s2 = opt.update(g, s, p)
                    return (optax.apply_updates(p, upd), s2), None

                (p, s), _ = jax.lax.scan(body, (p, s), None, length=k)
                return _scalar(p)

            return run

        results[mode] = chained_seconds_per_iter(build, (grads, state, tree),
                                                 deadline=deadline)
    return results


def bench_l2norm(tree, grads, deadline=None):
    from apex_tpu.ops.multi_tensor import flatten_pytree, multi_tensor_l2norm
    from apex_tpu.optimizers._fused_kernels import l2norm_flat

    flat, _ = flatten_pytree(grads, dtype=jnp.float32)
    tree_fn = lambda g: multi_tensor_l2norm(jax.tree_util.tree_leaves(g))
    flat_fn = l2norm_flat
    # sanity: both engines agree before we time them
    a, b = jax.jit(tree_fn)(grads), jax.jit(flat_fn)(flat)
    assert jnp.allclose(a, b, rtol=1e-5), (a, b)

    def build_tree(k):
        def run(g):
            # The 1e-30 carry nudge serializes the chained reductions (and
            # defeats loop-invariant hoisting of per-leaf partial sums). XLA
            # fuses the add into the reduction's read pass, but the timed
            # body is still norm-of-a-freshly-produced-tensor, not a bare
            # reduction; both variants pay it.
            def body(c, _):
                g2 = jax.tree_util.tree_map(lambda x: x + c * 1e-30, g)
                return tree_fn(g2), None

            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c

        return run

    def build_flat(k):
        def run(f):
            def body(c, _):
                return flat_fn(f + c * 1e-30), None

            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=k)
            return c

        return run

    return {
        "tree": chained_seconds_per_iter(build_tree, (grads,), deadline=deadline),
        "flat": chained_seconds_per_iter(build_flat, (flat,), deadline=deadline),
    }


def bench_adam_vs_torch_eager(tree, grads, ours_tree_sec):
    """BASELINE.md's second headline: "FusedAdam step time vs eager".

    The reference's FusedAdam exists to beat eager per-tensor torch.optim
    steps (SURVEY.md L4; amp_C.multi_tensor_adam).  Here the eager baseline
    is torch.optim.AdamW on CPU over the same tensors — measured directly
    (torch CPU ops are synchronous) —
    vs ``fused_adam(fuse="tree")`` jitted, slope-timed.  CPU-only: torch has
    no TPU backend, so this row is skipped on TPU runs.
    """
    import time

    import torch

    leaves = jax.tree_util.tree_leaves(tree)
    tparams = [
        torch.nn.Parameter(torch.from_numpy(__import__("numpy").asarray(x)).clone())
        for x in leaves
    ]
    tgrads = [
        torch.from_numpy(__import__("numpy").asarray(g)).clone()
        for g in jax.tree_util.tree_leaves(grads)
    ]
    for p, g in zip(tparams, tgrads):
        p.grad = g
    opt = torch.optim.AdamW(tparams, lr=1e-3, weight_decay=0.01)
    opt.step()  # state init outside the timed region
    n = 20
    t0 = time.perf_counter()
    for _ in range(n):
        opt.step()
    torch_sec = (time.perf_counter() - t0) / n
    # ours: reuse bench_adam's fuse="tree" measurement — same build closure,
    # already slope-timed once this run
    return {"torch_eager": torch_sec, "fused_tree": ours_tree_sec}


def bench_layer_norm(batch, hidden, key, deadline=None):
    from apex_tpu.ops.layer_norm import layer_norm

    x = jax.random.normal(key, (batch, hidden), jnp.float32)
    w = jnp.ones((hidden,))
    b = jnp.zeros((hidden,))
    out = {}
    for impl in ("xla", "pallas"):

        def build(k, impl=impl):
            def run(x, w, b):
                def body(c, _):
                    return layer_norm(c, w, b, impl=impl), None

                c, _ = jax.lax.scan(body, x, None, length=k)
                return _scalar(c)

            return run

        out[impl] = chained_seconds_per_iter(build, (x, w, b), deadline=deadline)
    return out


def bench_attention(batch, heads, seq, dim, key, deadline=None):
    from apex_tpu.ops.attention import flash_attention

    q = jax.random.normal(key, (batch, heads, seq, dim), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (batch, heads, seq, dim), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (batch, heads, seq, dim), jnp.bfloat16)
    out = {}
    for impl in ("xla", "pallas"):

        def build(n, impl=impl):
            def run(q, k, v):
                def body(c, _):
                    return flash_attention(c, k, v, causal=True, impl=impl), None

                c, _ = jax.lax.scan(body, q, None, length=n)
                return _scalar(c)

            return run

        out[impl] = chained_seconds_per_iter(build, (q, k, v), deadline=deadline)
    return out


def bench_attention_long(key, batch=1, heads=8, seq=16384, dim=128, deadline=None):
    """Single-chip long context: at 16k bf16 keys the kernel's resident-K/V
    budget is exceeded, so auto dispatch runs the blockwise tiled path —
    this row records what that path actually costs per step on hardware
    (and would OOM/page with the dense XLA fallback)."""
    from apex_tpu.ops.attention import flash_attention

    q = jax.random.normal(key, (batch, heads, seq, dim), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), q.shape, jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), q.shape, jnp.bfloat16)

    def build(n):
        def run(q, k, v):
            def body(c, _):
                return flash_attention(c, k, v, causal=True, impl="blockwise"), None

            c, _ = jax.lax.scan(body, q, None, length=n)
            return _scalar(c)

        return run

    sec = chained_seconds_per_iter(build, (q, k, v), reps=2, deadline=deadline)
    # causal flops: 2 dots x b h s^2/2 d x 2 (MACs)
    tflops = 2 * 2 * batch * heads * (seq * seq / 2) * dim / sec / 1e12
    return {"blockwise": sec, "tflops": round(tflops, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--params", type=int, default=None,
                    help="approx. total parameter count (default: 30M on TPU, 3M on CPU)")
    ap.add_argument("--json", action="store_true", help="emit one JSON line only")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args()

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    from apex_tpu.ops._dispatch import on_tpu

    tpu = on_tpu()
    n_params = args.params or (30_000_000 if tpu else 3_000_000)

    key = jax.random.PRNGKey(0)
    tree = make_param_tree(n_params, key)
    total = sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))
    grads = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.fold_in(key, 99), x.shape, x.dtype) * 1e-3,
        tree,
    )

    if tpu:
        ln_shape, attn_shape = (8192, 4096), (4, 16, 2048, 128)
    else:
        ln_shape, attn_shape = (512, 1024), (1, 4, 256, 64)

    record = {
        "platform": platform,
        "pallas_compiled": bool(tpu),  # False => Pallas rows resolved to XLA
        "n_params": total,
        "adam_step_s": bench_adam(tree, grads),
        "l2norm_s": bench_l2norm(tree, grads),
        "layer_norm_s": bench_layer_norm(*ln_shape, jax.random.fold_in(key, 7)),
        "attention_s": bench_attention(*attn_shape, jax.random.fold_in(key, 8)),
    }
    if not tpu:  # torch has no TPU backend; eager baseline is CPU-only
        record["adam_vs_eager_s"] = bench_adam_vs_torch_eager(
            tree, grads, record["adam_step_s"]["tree"]
        )
    if args.json:
        print(json.dumps(record))
        return

    print(f"platform={platform}  pallas_compiled={tpu}  params={total:,}")
    rows = ["adam_step_s", "l2norm_s", "layer_norm_s", "attention_s"]
    if "adam_vs_eager_s" in record:
        rows.append("adam_vs_eager_s")
    for name in rows:
        row = record[name]
        (k1, v1), (k2, v2) = row.items()
        ratio = v1 / v2 if v2 else float("inf")
        print(f"{name:14s}  {k1}={v1 * 1e3:9.3f} ms   {k2}={v2 * 1e3:9.3f} ms   "
              f"{k1}/{k2}={ratio:.2f}x")


if __name__ == "__main__":
    main()
