"""Compiled (Mosaic) smoke of every Pallas kernel on the TPU chip.

Off the chip the Pallas paths only ever run in interpret mode, which proves
the kernel's arithmetic and nothing about what Mosaic compiles it to. This
harness force-dispatches ``impl="pallas"`` on the TPU backend — compiled, not
interpreted — and checks numerics against the XLA reference implementation
for fwd AND bwd of each kernel, outside any timed window.

Every check is an independently named thunk, so one Mosaic lowering error
costs one verdict, not the rest of the list. Exit code: 0 only when every
check passed on a TPU; 1 on any mismatch, lowering error, or no TPU.

Run (through the chip tool): python benchmarks/tpu_kernel_smoke.py
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


_emit = functools.partial(print, flush=True)


def check(name, got, want, tol):
    """Every leaf of ``got`` within ``tol`` of ``want``, as a fraction of
    the leaf's scale (max |want|, at least 1): a bf16 dgamma summed over a
    thousand rows is in the hundreds, where ONE bf16 ulp is 1.0 — an
    absolute bound fit for dx fails it on rounding alone (first chip run of
    this smoke: LN/RMS bwd bf16 'failed' at 0.25-1.0 abs, under one ulp)."""
    got = jax.tree_util.tree_leaves(got)
    want = jax.tree_util.tree_leaves(want)
    if len(got) != len(want):
        _emit(f"FAIL {name}: tree mismatch")
        return False
    worst = 0.0
    for g, w in zip(got, want):
        w = w.astype(jnp.float32)
        scale = max(1.0, float(jnp.max(jnp.abs(w))))
        err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))) / scale
        if not np.isfinite(err) or err > tol:
            _emit(f"FAIL {name}: max err {err:.3g} of scale {scale:.3g} "
                  f"> {tol}")
            return False
        worst = max(worst, err)
    _emit(f"ok   {name} (max err {worst:.2g} of scale)")
    return True


def build_checks():
    """Yield (name, thunk) pairs; inputs are built inside each thunk."""
    key = jax.random.PRNGKey(0)

    # ---- layer norm / rms norm fwd+bwd ----
    # (512, 1024) runs the bwd dgamma/dbeta accumulation at grid>1
    # (block_rows=256 -> 2 grid steps; a per-step partials layout is
    # rejected by Mosaic's 8-sublane rule), and (1024, 4096) is the shape
    # whose fp32 temporaries blow the 16MB scoped-vmem limit unless
    # _pick_block_rows budgets 1MB/operand. bf16 at 4096: grid>1 + wide
    # hidden + bf16.
    from apex_tpu.ops import layer_norm, rms_norm

    def ln_inputs(rows, hidden, dtype):
        x = jax.random.normal(key, (rows, hidden), jnp.float32).astype(dtype)
        w = (jax.random.normal(jax.random.fold_in(key, 1), (hidden,)) * 0.1 + 1.0).astype(dtype)
        b = (jax.random.normal(jax.random.fold_in(key, 2), (hidden,)) * 0.1).astype(dtype)
        return x, w, b

    for rows, hidden, dtype, ftol, btol in [
        (512, 1024, jnp.float32, 2e-5, 2e-4),
        (1024, 4096, jnp.float32, 2e-5, 2e-3),
        (512, 1024, jnp.bfloat16, 2e-2, 2e-2),
        (1024, 4096, jnp.bfloat16, 3e-2, 3e-2),
    ]:
        tag = f"{rows}x{hidden} {jnp.dtype(dtype).name}"
        for opname, fn in [
            ("layer_norm", lambda impl: lambda x, w, b: layer_norm(x, w, b, impl=impl)),
            ("rms_norm", lambda impl: lambda x, w, b: rms_norm(x, w, impl=impl)),
        ]:
            def fwd(name=f"{opname} fwd {tag}", fn=fn, shape=(rows, hidden),
                    dtype=dtype, tol=ftol):
                x, w, b = ln_inputs(*shape, dtype)
                f_p = jax.jit(lambda x, w, b, f=fn("pallas"): f(x, w, b))
                f_x = jax.jit(lambda x, w, b, f=fn("xla"): f(x, w, b))
                return check(name, f_p(x, w, b), f_x(x, w, b), tol)

            def bwd(name=f"{opname} bwd {tag}", fn=fn, shape=(rows, hidden),
                    dtype=dtype, tol=btol):
                x, w, b = ln_inputs(*shape, dtype)
                g_p = jax.jit(jax.grad(lambda x, w, b, f=fn("pallas"): jnp.sum(jnp.sin(f(x, w, b).astype(jnp.float32))), argnums=(0, 1, 2)))
                g_x = jax.jit(jax.grad(lambda x, w, b, f=fn("xla"): jnp.sum(jnp.sin(f(x, w, b).astype(jnp.float32))), argnums=(0, 1, 2)))
                return check(name, g_p(x, w, b), g_x(x, w, b), tol)

            yield f"{opname} fwd {tag}", fwd
            yield f"{opname} bwd {tag}", bwd

    # ---- flash attention fwd+bwd (causal + non-causal) ----
    # Tolerances are hardware-calibrated, not wishful: on TPU the fp32 dots in
    # BOTH paths run at MXU default precision (bf16 passes), and measured
    # distance-from-fp64-ground-truth on v5e is ~3e-3 (non-causal) / ~1e-2
    # (causal) for EACH path, with Pallas slightly closer to fp64 than XLA.
    # The pallas-vs-xla delta is precision noise, so the gate is set at the
    # 2x-the-measured-noise level rather than an fp32-exactness fantasy.
    from apex_tpu.ops import flash_attention

    def qkv(kq=3, kk=4, kv=5, hq=4, hkv=4, seq=256):
        q = jax.random.normal(jax.random.fold_in(key, kq), (2, hq, seq, 64), jnp.float32)
        k_ = jax.random.normal(jax.random.fold_in(key, kk), (2, hkv, seq, 64), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, kv), (2, hkv, seq, 64), jnp.float32)
        return q, k_, v

    for causal in (False, True):
        def fa_fwd(name=f"flash_attention fwd causal={causal}", c=causal):
            q, k_, v = qkv()
            f_p = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=c, impl="pallas"))
            f_x = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=c, impl="xla"))
            return check(name, f_p(q, k_, v), f_x(q, k_, v), 2e-2)

        def fa_bwd(name=f"flash_attention bwd causal={causal}", c=causal):
            q, k_, v = qkv()
            g_p = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(q, k, v, causal=c, impl="pallas"))), argnums=(0, 1, 2)))
            g_x = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(q, k, v, causal=c, impl="xla"))), argnums=(0, 1, 2)))
            return check(name, g_p(q, k_, v), g_x(q, k_, v), 5e-2)

        yield f"flash_attention fwd causal={causal}", fa_fwd
        yield f"flash_attention bwd causal={causal}", fa_bwd

    # ---- GQA / sliding window / key-padding fast paths (compiled) ----
    def gqa_fwd(name="flash_attention GQA fwd"):
        q4, k4, v4 = qkv(10, 11, 12, hq=4, hkv=2)
        gq_p = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, impl="pallas"))
        gq_x = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, impl="xla"))
        return check(name, gq_p(q4, k4, v4), gq_x(q4, k4, v4), 2e-2)

    def gqa_bwd(name="flash_attention GQA bwd"):
        q4, k4, v4 = qkv(10, 11, 12, hq=4, hkv=2)
        gg_p = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            flash_attention(q, k, v, causal=True, impl="pallas"))), argnums=(0, 1, 2)))
        gg_x = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            flash_attention(q, k, v, causal=True, impl="xla"))), argnums=(0, 1, 2)))
        return check(name, gg_p(q4, k4, v4), gg_x(q4, k4, v4), 5e-2)

    def window_fwd(name="flash_attention window fwd"):
        q, k_, v = qkv()
        w_p = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=100, impl="pallas"))
        w_x = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=100, impl="xla"))
        return check(name, w_p(q, k_, v), w_x(q, k_, v), 2e-2)

    def kpm_fwd(name="flash_attention kpm fwd"):
        q, k_, v = qkv()
        kpm = jnp.zeros((2, 256), bool).at[0, 180:].set(True)
        kp_p = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, key_padding_mask=kpm, impl="pallas"))
        kp_x = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, key_padding_mask=kpm, impl="xla"))
        return check(name, kp_p(q, k_, v), kp_x(q, k_, v), 2e-2)

    def kpm_bwd(name="flash_attention kpm bwd"):
        # batch 2: the (b, sk) mask at b > 1 is the case the TPU lowering
        # once refused (_kpm_spec)
        q, k_, v = qkv()
        kpm = jnp.zeros((2, 256), bool).at[0, 180:].set(True)
        gk_p = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, key_padding_mask=kpm, impl="pallas"))), argnums=(0, 1, 2)))
        gk_x = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, key_padding_mask=kpm, impl="xla"))), argnums=(0, 1, 2)))
        return check(name, gk_p(q, k_, v), gk_x(q, k_, v), 5e-2)

    def gpt2_bwd(name="flash_attention bwd bf16 causal s=1024 d=64"):
        # the GPT-2 345M trainer's own attention call (chip_smoke.py)
        q, k_, v = (x.astype(jnp.bfloat16)
                    for x in qkv(13, 14, 15, hq=16, hkv=16, seq=1024))
        loss = lambda impl: lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=True, impl=impl).astype(jnp.float32)))
        g_p = jax.jit(jax.grad(loss("pallas"), argnums=(0, 1, 2)))
        g_x = jax.jit(jax.grad(loss("xla"), argnums=(0, 1, 2)))
        return check(name, g_p(q, k_, v), g_x(q, k_, v), 1e-1)

    def mla_bwd(name="flash_attention bwd bf16 causal s=4096 d=192/128"):
        # latent attention's call (joyai_llm_flash.pretrain): q and k 192
        # wide, v and the output 128, four heads of it (the XLA side holds
        # their (s, s) scores whole)
        ks = [jax.random.fold_in(key, n) for n in (30, 31, 32)]
        q = jax.random.normal(ks[0], (1, 4, 4096, 192), jnp.bfloat16)
        k_ = jax.random.normal(ks[1], (1, 4, 4096, 192), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 4, 4096, 128), jnp.bfloat16)
        loss = lambda impl: lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=True, impl=impl).astype(jnp.float32)))
        g_p = jax.jit(jax.grad(loss("pallas"), argnums=(0, 1, 2)))
        g_x = jax.jit(jax.grad(loss("xla"), argnums=(0, 1, 2)))
        return check(name, g_p(q, k_, v), g_x(q, k_, v), 1e-1)

    def latent_bwd(name="latent_flash_attention bwd bf16 s=4096 "
                        "d=128+64/128 kpm"):
        # the same call as the projections write it (batch-major rows, a
        # head a column range, ONE rope key, both rope parts unrotated):
        # two batch rows of four heads, forward and five gradients, the
        # compiled kernels against the assembled XLA path
        from apex_tpu.ops import rope_frequencies
        from apex_tpu.ops.attention import latent_flash_attention

        ks = [jax.random.fold_in(key, n) for n in range(40, 45)]
        shapes = ((2, 4096, 4 * 128), (2, 4096, 4 * 64), (2, 4096, 4 * 256),
                  (2, 4096, 64))
        args = [jax.random.normal(k, sh, jnp.bfloat16)
                for k, sh in zip(ks, shapes)]
        freqs = rope_frequencies(64, 4096, interleaved=True)
        pad = jnp.zeros((2, 4096), bool).at[1, 1000:1100].set(True)
        loss = lambda impl: lambda *a: jnp.sum(jnp.sin(latent_flash_attention(
            *a, freqs, heads=4, interleaved=True, key_padding_mask=pad,
            impl=impl).astype(jnp.float32)))
        both = lambda impl: jax.jit(jax.value_and_grad(
            loss(impl), argnums=(0, 1, 2, 3)))(*args)
        return check(name, both("pallas"), both("xla"), 1e-1)

    yield "flash_attention GQA fwd", gqa_fwd
    yield "flash_attention GQA bwd", gqa_bwd
    yield "flash_attention window fwd", window_fwd
    yield "flash_attention kpm fwd", kpm_fwd
    yield "flash_attention kpm bwd", kpm_bwd
    yield "flash_attention bwd bf16 causal s=1024 d=64", gpt2_bwd
    yield "flash_attention bwd bf16 causal s=4096 d=192/128", mla_bwd
    yield "latent_flash_attention bwd bf16 s=4096 d=128+64/128 kpm", latent_bwd

    # ---- blockwise long-context + decode-shaped attention (compiled) ----
    # The blockwise path is the single-chip long-context engine
    # (ops/attention.py _attn_blockwise); seq=300 is deliberately
    # non-divisible so the padded-tail chunking compiles too.
    def qkv_long():
        qL = jax.random.normal(jax.random.fold_in(key, 20), (1, 4, 300, 64), jnp.float32)
        kL = jax.random.normal(jax.random.fold_in(key, 21), (1, 4, 300, 64), jnp.float32)
        vL = jax.random.normal(jax.random.fold_in(key, 22), (1, 4, 300, 64), jnp.float32)
        return qL, kL, vL

    kpmL_spec = lambda: jnp.zeros((1, 300), bool).at[0, 250:].set(True)
    for tag, kw in [
        ("causal", dict(causal=True)),
        ("window", dict(causal=True, window=64)),
        ("kpm", "kpm"),
    ]:
        def bw_fwd(name=f"blockwise {tag} fwd", kw=kw):
            qL, kL, vL = qkv_long()
            kw2 = dict(key_padding_mask=kpmL_spec()) if kw == "kpm" else kw
            b_p = jax.jit(lambda q, k, v: flash_attention(q, k, v, impl="blockwise", **kw2))
            b_x = jax.jit(lambda q, k, v: flash_attention(q, k, v, impl="xla", **kw2))
            return check(name, b_p(qL, kL, vL), b_x(qL, kL, vL), 2e-2)

        def bw_bwd(name=f"blockwise {tag} bwd", kw=kw):
            qL, kL, vL = qkv_long()
            kw2 = dict(key_padding_mask=kpmL_spec()) if kw == "kpm" else kw
            gb_p = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
                flash_attention(q, k, v, impl="blockwise", **kw2))), argnums=(0, 1, 2)))
            gb_x = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
                flash_attention(q, k, v, impl="xla", **kw2))), argnums=(0, 1, 2)))
            return check(name, gb_p(qL, kL, vL), gb_x(qL, kL, vL), 5e-2)

        yield f"blockwise {tag} fwd", bw_fwd
        yield f"blockwise {tag} bwd", bw_bwd

    # decode hot path: one query token against a 256-slot KV cache with the
    # unwritten tail padded out — exactly the call transformer/layer.py:418
    # makes per generated token (causal=False + kpm, sq=1)
    def decode_fwd(name="decode sq=1 kpm fwd"):
        _, k_, v = qkv()
        qd = jax.random.normal(jax.random.fold_in(key, 23), (2, 4, 1, 64), jnp.float32)
        kpm_d = jnp.broadcast_to(jnp.arange(256)[None, :] > 200, (2, 256))
        d_p = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, key_padding_mask=kpm_d, impl="pallas"))
        d_x = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, key_padding_mask=kpm_d, impl="xla"))
        return check(name, d_p(qd, k_, v), d_x(qd, k_, v), 2e-2)

    yield "decode sq=1 kpm fwd", decode_fwd

    # the serving engine's decode: one token a lane against a paged pool,
    # by block table and length (ragged lengths, a window, grouped heads,
    # an idle lane whose table is all sentinel)
    def paged(heads, h_kv, hd, dtype, window, tol):
        from apex_tpu.ops.attention import paged_decode_attention

        nb, bs, mb = 192, 16, 64
        lengths = np.array([1, 15, 16, 17, 255, 256, 257, 900, 1024, 1],
                           np.int32)
        rng = np.random.RandomState(7)
        tables = np.full((lengths.size, mb), nb, np.int32)
        order, used = rng.permutation(nb), 0
        for lane, n in enumerate(lengths[:-1]):
            need = -(-int(n) // bs)
            tables[lane, :need] = order[used:used + need]
            used += need
        ks = jax.random.split(jax.random.fold_in(key, 29), 3)
        q = jax.random.normal(ks[0], (lengths.size, heads, hd), dtype)
        kp, vp = (jax.random.normal(k, (nb, bs, h_kv * hd), dtype)
                  for k in ks[1:])
        run = lambda impl: jax.jit(lambda *a: paged_decode_attention(
            *a, scale=hd ** -0.5, window=window, impl=impl))(
                q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths))
        return check(f"paged_decode {heads}/{h_kv} x {hd} "
                     f"{jnp.dtype(dtype).name} window {window}",
                     run("pallas"), run("xla"), tol)

    for case in ((16, 16, 64, jnp.bfloat16, None, 2e-2),
                 (16, 16, 64, jnp.float32, None, 2e-2),
                 (32, 8, 128, jnp.bfloat16, 300, 2e-2)):
        yield f"paged_decode {case[:3]}", functools.partial(paged, *case)

    # ---- flat optimizer engine ----
    # 3 chunks: the production case is a MULTI-chunk buffer (grid > 1), which
    # exercises the sequential-grid accumulation in l2norm_flat and the
    # per-chunk block walk in adam_flat — grid=1 alone would leave the same
    # hazard class that bit the LN bwd partials (see above) uncovered
    def flat_inputs():
        from apex_tpu.ops.multi_tensor import CHUNK_SIZE

        n = 3 * CHUNK_SIZE
        buf = jax.random.normal(jax.random.fold_in(key, 8), (n,), jnp.float32)
        g = jax.random.normal(jax.random.fold_in(key, 9), (n,), jnp.float32)
        return buf, g

    def adam_check(name="adam_flat"):
        from apex_tpu.optimizers._fused_kernels import adam_flat

        buf, g = flat_inputs()
        m = jnp.zeros_like(buf)
        v2 = jnp.zeros_like(buf)
        bc1, bc2 = jnp.float32(1 - 0.9), jnp.float32(1 - 0.999)
        adam = lambda impl: jax.jit(
            lambda g, p, m, v, bc1, bc2: adam_flat(
                g, p, m, v, bc1, bc2, lr=1e-3, beta1=0.9, beta2=0.999,
                eps=1e-8, weight_decay=0.01, adam_w_mode=True, impl=impl)
        )
        return check(name, adam("pallas")(g, buf, m, v2, bc1, bc2),
                     adam("xla")(g, buf, m, v2, bc1, bc2), 1e-6)

    def l2norm_check(name="l2norm_flat"):
        from apex_tpu.optimizers._fused_kernels import l2norm_flat

        buf, _ = flat_inputs()
        n_p = jax.jit(lambda x: l2norm_flat(x, impl="pallas"))(buf)
        n_x = jax.jit(lambda x: l2norm_flat(x, impl="xla"))(buf)
        return check(name, n_p, n_x, 1e-2)

    yield "adam_flat", adam_check
    yield "l2norm_flat", l2norm_check


def main():
    """Run every kernel check; returns the process exit code."""
    from apex_tpu.ops._dispatch import on_tpu

    dev = jax.devices()[0]
    _emit(f"backend: {dev.platform} / {dev.device_kind}")
    if not on_tpu():
        # impl="pallas" off a TPU is the interpreter: every check would pass
        # without Mosaic compiling a single kernel
        _emit("FAILURES (no TPU: nothing here would be compiled)")
        return 1
    ok = True
    for name, thunk in build_checks():
        try:
            ok &= bool(thunk())
        except Exception as e:  # a lowering error is this check's verdict
            _emit(f"FAIL {name}: raised {e!r:.300}")
            ok = False
    _emit("ALL OK" if ok else "FAILURES")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
