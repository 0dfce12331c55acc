"""Small-shape (openfold-tier) micro-benchmarks.

Reference parity: apex/contrib/openfold_triton ships shape-specialized
kernels (LayerNormSmallShapeOptImpl, small fused MHA) because at
AlphaFold-ish shapes — LN over a few thousand SHORT rows, attention with
seq <= 256 and tiny head counts — launch overhead and tile underfill
dominate and the generic CUDA kernels lose.  The TPU question is
different: do the generic Pallas kernels lose to plain XLA at these
shapes (tile underfill on 8x128 lanes), and by how much?  This harness
measures exactly that, with the same slope-timing method as the rest of
the suite. Only a run on the chip answers it; the claim "subsumed by the
ops kernels" is not measured until one has been made.

Shapes follow openfold's evoformer: LN hidden 64/128 (pair/msa channels)
over many rows; MHA seq 128/256, head_dim 8/16 (!), few heads.

Usage: python benchmarks/bench_small_shapes.py [--cpu] [--json]
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

# (rows, hidden): evoformer LN shapes — MANY short rows
LN_SHAPES = [(16384, 64), (4096, 128)]
# (batch*? , heads, seq, head_dim): evoformer attention shapes
MHA_SHAPES = [(8, 4, 128, 16), (4, 8, 256, 8)]


def bench_ln_small(rows, hidden, key, deadline=None):
    from apex_tpu.ops.layer_norm import layer_norm

    x = jax.random.normal(key, (rows, hidden), jnp.float32)
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

        out[impl] = chained_seconds_per_iter(build, (x, w, b),
                                             deadline=deadline)
    return out


def bench_mha_small(b, h, s, d, key, deadline=None):
    from apex_tpu.ops.attention import flash_attention

    q = jax.random.normal(key, (b, h, s, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, s, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, s, d), jnp.float32)
    out = {}
    for impl in ("xla", "pallas"):

        def build(n, impl=impl):
            def run(q, k, v):
                def body(c, _):
                    return flash_attention(c, k, v, impl=impl), None

                c, _ = jax.lax.scan(body, q, None, length=n)
                return _scalar(c)

            return run

        out[impl] = chained_seconds_per_iter(build, (q, k, v),
                                             deadline=deadline)
    return out


def run_all(key, deadline=None):
    rec = {}
    for rows, hidden in LN_SHAPES:
        rec[f"ln_{rows}x{hidden}_s"] = bench_ln_small(
            rows, hidden, jax.random.fold_in(key, hidden), deadline
        )
    for shape in MHA_SHAPES:
        rec["mha_%dx%dx%dx%d_s" % shape] = bench_mha_small(
            *shape, jax.random.fold_in(key, shape[2]), deadline
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (see bench_optimizers docstring)")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    from apex_tpu.ops._dispatch import on_tpu

    rec = {"platform": platform, "pallas_compiled": bool(on_tpu())}
    rec.update(run_all(jax.random.PRNGKey(0)))
    if args.json:
        print(json.dumps(rec))
        return
    print(f"platform={platform}  pallas_compiled={rec['pallas_compiled']}")
    for name, row in rec.items():
        if not isinstance(row, dict):
            continue
        ratio = row["xla"] / row["pallas"] if row["pallas"] else float("inf")
        print(f"{name:22s}  xla={row['xla'] * 1e3:8.3f} ms   "
              f"pallas={row['pallas'] * 1e3:8.3f} ms   xla/pallas={ratio:.2f}x")


if __name__ == "__main__":
    main()
