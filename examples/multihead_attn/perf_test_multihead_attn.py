"""Multihead-attention throughput harness.

Reference parity: apex/contrib/examples/multihead_attn/
perf_test_multihead_attn.py — the user-runnable script that sweeps batch
size and prints attention throughput per configuration.  Same sweep and
flag surface here, with the two TPU-required changes:

- timing is the chained-scan SLOPE (``apex_tpu.utils.benchmarking``), not
  wall clock around a synchronize — the slope cancels every per-call
  constant (dispatch, fetch; docs/benchmarking.md);
- ``--ref`` selects the unfused jnp composition instead of the fused
  module (the reference's 'default' impl), and ``--fwd`` times forward
  only (otherwise fwd+bwd via ``jax.grad``, like the reference's
  ``.backward()`` loop).

Run: python examples/multihead_attn/perf_test_multihead_attn.py
     [--seq-length 64] [--num-seqs-start 10 --num-seqs-stop 120
      --num-seqs-inc 5] [--layers 18] [--hidden-dim 1024] [--heads 16]
     [--encdec-attn] [--norm-add] [--biases] [--fwd] [--ref] [--cpu]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import jax
import jax.numpy as jnp


def main():
    p = argparse.ArgumentParser(description="Multihead Attention Standalone Test")
    p.add_argument("--seq-length", default=64, type=int)
    p.add_argument("--num-seqs-start", default=10, type=int)
    p.add_argument("--num-seqs-stop", default=120, type=int)
    p.add_argument("--num-seqs-inc", default=5, type=int)
    p.add_argument("--layers", default=18, type=int,
                   help="attention layers chained per step (ref overlap knob)")
    p.add_argument("--hidden-dim", default=1024, type=int)
    p.add_argument("--heads", default=16, type=int)
    p.add_argument("--encdec-attn", action="store_true")
    p.add_argument("--norm-add", action="store_true")
    p.add_argument("--biases", action="store_true")
    p.add_argument("--fwd", action="store_true", help="forward pass only")
    p.add_argument("--ref", action="store_true",
                   help="unfused jnp composition instead of the flash path")
    p.add_argument("--cpu", action="store_true", help="force the CPU backend")
    args = p.parse_args()

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from apex_tpu.contrib.multihead_attn import (
        EncdecMultiheadAttn,
        SelfMultiheadAttn,
    )
    from apex_tpu.utils.benchmarking import chained_seconds_per_iter, full_reduce

    impl = "xla" if args.ref else "auto"
    cls = EncdecMultiheadAttn if args.encdec_attn else SelfMultiheadAttn
    layer = cls(
        embed_dim=args.hidden_dim,
        num_heads=args.heads,
        dropout=0.0,  # deterministic timing, like the ref's eval-mode runs
        bias=args.biases,
        include_norm_add=args.norm_add,
        impl=impl,
    )
    dev = jax.devices()[0]
    print(f"backend: {dev.platform} / {dev.device_kind}   "
          f"{'encdec' if args.encdec_attn else 'self'}-attn  "
          f"hidden {args.hidden_dim}  heads {args.heads}  "
          f"seq {args.seq_length}  layers {args.layers}  "
          f"{'fwd' if args.fwd else 'fwd+bwd'}  impl={impl}")

    key = jax.random.PRNGKey(111)
    for seqs in range(args.num_seqs_start, args.num_seqs_stop + 1,
                      args.num_seqs_inc):
        shape = (args.seq_length, seqs, args.hidden_dim)
        x = jax.random.normal(key, shape, jnp.float32)
        if args.encdec_attn:
            params = layer.init(key, x, x)
            apply = lambda p, x: layer.apply(p, x, x)
        else:
            params = layer.init(key, x)
            apply = layer.apply

        def stack(p, x):
            for _ in range(args.layers):
                x = apply(p, x)
            return x

        if args.fwd:
            def build(k):
                def run(p, x):
                    def body(c, _):
                        return stack(p, c), None

                    c, _ = jax.lax.scan(body, x, None, length=k)
                    return full_reduce(c)

                return run
        else:
            def build(k):
                def run(p, x):
                    def body(c, _):
                        g = jax.grad(
                            lambda xx: jnp.sum(jnp.square(stack(p, xx)))
                        )(c)
                        return g, None

                    c, _ = jax.lax.scan(body, x, None, length=k)
                    return full_reduce(c)

                return run

        sec = chained_seconds_per_iter(build, (params, x), reps=2)
        per_layer_us = sec / args.layers * 1e6
        elems = args.seq_length * seqs
        print(f"seqs {seqs:4d}   {sec * 1e3:9.3f} ms/iter   "
              f"{per_layer_us:9.1f} us/layer   "
              f"{elems / sec / 1e6:8.2f} Mtok/s ({'fwd' if args.fwd else 'fwd+bwd'})")


if __name__ == "__main__":
    main()
