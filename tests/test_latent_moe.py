"""Latent attention, the sigmoid-routed dropless expert layer with its share,
multi-token prediction, and the described model through
``build_gpt_training`` — each against an oracle written out here or the
benchmark's plain reference (``perf/reference/joyai_llm_flash.py``, which
imports nothing of the program). CPU, small sizes, seeded."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.compat import shard_map
from apex_tpu.models import GPTModel, gpt_mtp_loss_fn
from apex_tpu.models.arch import described_model
from apex_tpu.ops import attention as A
from apex_tpu.ops.rope import apply_rotary_pos_emb, rope_frequencies
from apex_tpu.transformer import TransformerConfig
from apex_tpu.transformer.layer import LatentAttention
from apex_tpu.transformer.moe import MoEMLP
from perf import joyai_tree
from perf.reference import joyai_llm_flash as ref

ARCH = dict(
    model_type="joyai_llm_flash", hidden_size=64, num_attention_heads=4,
    num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, n_shared_experts=1,
    num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=2.5,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rms_norm_eps=1e-6, rope_theta=32e6,
    rope_interleave=True, vocab_size=128, num_nextn_predict_layers=1)
DIMS = dict(layers=3, hidden=64, heads=4, vocab=128, q_rank=48, kv_rank=32,
            nope=16, rope=8, v_dim=16, dense_ffn=96, expert_ffn=32,
            experts=16, held=4, shared=1, dense_layers=1)
REF_KW = dict(heads=4, nope=16, rope=8, v_dim=16, first=4, top_k=4,
              theta=32e6, eps=1e-6, scale=2.5, precision="f32")
SEQ = 32


def config(held=4, first=4, **kw):
    _, model = described_model(ARCH, layers_kept=3, experts_held=held,
                               first_expert=first, vocab_rows=128)
    return TransformerConfig(
        num_layers=3, hidden_size=64, num_attention_heads=4, vocab_size=128,
        max_position_embeddings=SEQ, hidden_dropout=0.0,
        attention_dropout=0.0, compute_dtype=jnp.float32,
        **dict(model, **kw))


def close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


# -- flash attention with d_qk != d_v -----------------------------------------


@pytest.mark.parametrize("what", ["fwd", "dq", "dk", "dv"])
@pytest.mark.parametrize("shape", [(1, 2, 256, 192, 128, {}),
                                   (2, 2, 64, 24, 16,
                                    dict(block_q=32, block_k=32))])
def test_flash_kernels_take_wider_keys_than_values(shape, what):
    b, h, s, d_qk, d_v, kw = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(ks[i], (b, h, s, d_qk)) for i in (0, 1))
    v, do = (jax.random.normal(ks[i], (b, h, s, d_v)) for i in (2, 3))
    scale = d_qk ** -0.5
    flash = lambda q, k, v: A.flash_attention(
        q, k, v, causal=True, impl="pallas", **kw)
    plain = lambda q, k, v: A._attn_ref(q, k, v, scale, True)
    if what == "fwd":
        out = flash(q, k, v)
        assert out.shape == (b, h, s, d_v)
        return close(out, plain(q, k, v))
    arg = ("dq", "dk", "dv").index(what)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * do), arg)(q, k, v)
    close(grad(flash), grad(plain))


def test_blockwise_path_takes_wider_keys_than_values():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k = (jax.random.normal(ks[i], (1, 2, 48, 24)) for i in (0, 1))
    v = jax.random.normal(ks[2], (1, 2, 48, 16))
    f = lambda impl: lambda *a: jnp.sum(jnp.sin(A.flash_attention(
        *a, causal=True, impl=impl, block_q=2, block_k=2)))
    for got, want in zip(jax.grad(f("blockwise"), (0, 1, 2))(q, k, v),
                         jax.grad(f("xla"), (0, 1, 2))(q, k, v)):
        close(got, want)


def test_flash_metadata_names_both_head_dims():
    calls = A._flash_calls(2, 64, 64, 24, 16, (jnp.float32,) * 3, 2, 1, 0.2,
                           True, True, (32, 32), (32, 32), None, False)
    assert len(calls) == 3
    # latent attention's calls: the same three sites, a head a lane range
    parts = A._flash_calls(4, 64, 64, 128, 128, (jnp.float32,) * 3, 2, 1,
                           0.2, True, True, (32, 32), (32, 32), None, False,
                           64)
    assert len(parts) == 3 and parts != calls
    assert A._kv_vmem_bytes(4096, 192, 2, 128) == 4096 * (256 + 128) * 2
    assert A._kv_vmem_bytes(1024, 64, 2) == 2 * 1024 * 128 * 2
    # the latent call's residents: 128 + 128 (rope) lanes, and 128
    assert A._kv_vmem_bytes(4096, 128 + 128, 2, 128) == A._kv_vmem_bytes(
        4096, 192, 2, 128)


# -- the flash kernels on latent attention's own layouts ----------------------

LATENT_SHAPES = {
    # b, heads, s, nope, rope, d_v, forced tiles
    "s256": (2, 2, 256, 128, 64, 128, {}),
    "tiny-32x32": (2, 2, 64, 128, 64, 128, dict(block_q=32, block_k=32)),
}


def _latent_operands(shape, kpm):
    b, h, s, nope, rope, dv, _ = LATENT_SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    widths = (h * nope, h * rope, h * (nope + dv), rope, h * dv)
    q_nope, q_rope, kv, k_rope, do = (
        jax.random.normal(k, (b, s, w)) for k, w in zip(ks, widths))
    pad = None
    if kpm:  # a run of keys in one row, the first keys in the other: the
        # second row's first queries see no key at all (dead rows)
        pad = jnp.zeros((b, s), bool).at[0, s // 3:s // 2].set(True).at[
            1, :5].set(True)
    return (q_nope, q_rope, kv, k_rope), do, pad


@functools.lru_cache(maxsize=None)
def _latent_results(shape, kpm, impl):
    """(forward, gradients) of the latent entry and of ``_attn_ref`` on the
    assembled q, k, v, as {name: (got, want)}."""
    b, h, s, nope, rope, dv, kw = LATENT_SHAPES[shape]
    args, do, pad = _latent_operands(shape, kpm)
    freqs = rope_frequencies(rope, s, base=32e6, interleaved=True)

    def entry(*a):
        return A.latent_flash_attention(
            *a, freqs, heads=h, interleaved=True, key_padding_mask=pad,
            impl=impl, **kw)

    def plain(q_nope, q_rope, kv, k_rope):
        rot = functools.partial(
            apply_rotary_pos_emb, freqs=freqs.reshape(1, s, 1, rope),
            interleaved=True)
        heads_of = lambda t: t.reshape(b, s, h, -1)
        kv4 = heads_of(kv)
        q = jnp.concatenate([heads_of(q_nope), rot(heads_of(q_rope))], -1)
        k = jnp.concatenate([kv4[..., :nope], jnp.broadcast_to(
            rot(k_rope[:, :, None, :]), (b, s, h, rope))], -1)
        o = A._attn_ref(
            *(jnp.swapaxes(t, 1, 2) for t in (q, k, kv4[..., nope:])),
            (nope + rope) ** -0.5, True,
            None if pad is None else pad[:, None, None, :])
        return jnp.swapaxes(o, 1, 2).reshape(b, s, h * dv)

    out = {"fwd": (entry(*args), plain(*args))}
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * do), (0, 1, 2, 3))(
        *args) for f in (entry, plain)]
    for name, got, want in zip(("dq_nope", "dq_rope", "dkv", "dk_rope"),
                               *grads):
        out[name] = (got, want)
    # kv's gradient holds dk_nope and dv side by side, head by head
    dkv = [g.reshape(b, s, h, nope + dv) for g in out.pop("dkv")]
    out["dk_nope"] = tuple(g[..., :nope] for g in dkv)
    out["dv"] = tuple(g[..., nope:] for g in dkv)
    return out


@pytest.mark.parametrize(
    "what", ["fwd", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("kpm", [False, True], ids=["causal", "kpm"])
@pytest.mark.parametrize("shape", list(LATENT_SHAPES))
def test_latent_entry_matches_the_reference_on_assembled_q_k_v(
        shape, kpm, impl, what):
    """``dk_rope`` is the shared key's gradient: the sum over heads."""
    got, want = _latent_results(shape, kpm, impl)[what]
    assert got.shape == want.shape
    close(got, want)


def _eqns(jaxpr):
    """Every equation of a jaxpr, through its sub-jaxprs but not into
    Pallas kernel bodies (what a kernel does in VMEM moves nothing in
    HBM)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_latent_attention_moves_no_head_sized_activation():
    """The module's forward on the kernels' path: no concatenate, no
    broadcast to (..., heads, rope), no transpose of anything with tokens
    x heads x d elements; what the kernels are handed is what the
    projections wrote."""
    heads, nope, rope, dv, s, b = 4, 128, 64, 128, SEQ, 2
    cfg = config(qk_nope_head_dim=nope, qk_rope_head_dim=rope, v_head_dim=dv,
                 attention_impl="pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), (s, b, 64))
    freqs = rope_frequencies(rope, s, base=32e6, interleaved=True)
    mod = LatentAttention(config=cfg)
    params = mod.init(jax.random.PRNGKey(1), x, rotary_pos_emb=(freqs, None))
    jaxpr = jax.make_jaxpr(lambda p, x: mod.apply(
        p, x, rotary_pos_emb=(freqs, None)))(params, x)
    eqns = list(_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("pallas_call") == 2  # the rotation, the forward
    assert "concatenate" not in names
    head_sized = s * b * heads * min(rope, dv)
    for e in eqns:
        out = e.outvars[0].aval
        if e.primitive.name == "transpose":
            assert out.size < head_sized, out.shape
        if e.primitive.name == "broadcast_in_dim":
            assert out.shape[-2:] != (heads, rope), out.shape
    # the flash kernel's operands are the projections' outputs themselves:
    # the entry's rule is handed three matmuls' results, and inside it only
    # q's rope part passes through another kernel (the rotation)
    flash = [e for e in eqns if e.primitive.name == "pallas_call"][-1]
    assert [v.aval.shape for v in flash.invars] == [
        (b, s, heads * nope), (b, s, heads * rope), (b, s, heads * (nope + dv)),
        (b, s, 128)]
    assert flash.outvars[0].aval.shape == (b, s, heads * dv)
    produced_by = {v: e.primitive.name for e in eqns for v in e.outvars}
    rule = next(e for e in eqns if e.primitive.name.startswith("custom_vjp"))
    assert [produced_by[v] for v in rule.invars[:3]] == ["dot_general"] * 3
    assert produced_by[flash.invars[1]] == "pallas_call"
    consumer = next(e for e in eqns if rule.outvars[0] in e.invars)
    assert consumer.primitive.name == "dot_general"  # o_proj


# -- rope ---------------------------------------------------------------------


def test_interleaved_rope_rotates_consecutive_pairs_of_a_slice():
    s, d, rot = 6, 24, 8
    t = jax.random.normal(jax.random.PRNGKey(2), (s, 1, 2, d))
    freqs = rope_frequencies(rot, s, base=32e6, interleaved=True)
    got = np.asarray(apply_rotary_pos_emb(t, freqs, interleaved=True))
    want = np.asarray(t).copy()
    for pos in range(s):
        for i in range(rot // 2):
            ang = pos * 32e6 ** (-2 * i / rot)
            a, b = np.asarray(t)[pos, ..., 2 * i], np.asarray(t)[
                pos, ..., 2 * i + 1]
            want[pos, ..., 2 * i] = a * np.cos(ang) - b * np.sin(ang)
            want[pos, ..., 2 * i + 1] = b * np.cos(ang) + a * np.sin(ang)
    close(got, want)                       # channels past ``rot`` pass
    close(got[..., rot:], np.asarray(t)[..., rot:], 0)
    # and it is not the rotate-half layout
    half = apply_rotary_pos_emb(t, rope_frequencies(rot, s, base=32e6))
    assert float(jnp.max(jnp.abs(half - got))) > 1e-2


# -- latent attention ---------------------------------------------------------


def test_latent_attention_matches_the_reference_forward_and_gradients():
    cfg = config()
    w = ref.init_weights(ref.seed_key(5), **DIMS)
    lw = {k: w[k][0] for k in ref.ATTN_LEAVES}
    params = joyai_tree.to_program(w)["params"]["transformer"]["layer_0"][
        "self_attention"]
    x = jax.random.normal(jax.random.PRNGKey(3), (SEQ, 1, 64))
    rotary = (rope_frequencies(8, SEQ, base=32e6, interleaved=True),) * 2

    def program(p, x):
        return LatentAttention(config=cfg).apply(
            {"params": p}, x, rotary_pos_emb=rotary)

    def plain(lw, x):
        return ref._attention(x[:, 0], lw, heads=4, nope=16, rope=8,
                              v_dim=16, theta=32e6, eps=1e-6,
                              precision="f32")[:, None]

    close(program(params, x), plain(lw, x))
    probe = jax.random.normal(jax.random.PRNGKey(4), (SEQ, 1, 64))
    gp, gx = jax.grad(lambda p, x: jnp.sum(program(p, x) * probe),
                      (0, 1))(params, x)
    rp, rx = jax.grad(lambda l, x: jnp.sum(plain(l, x) * probe),
                      (0, 1))(lw, x)
    close(gx, rx)
    for name, path in joyai_tree._ATTN.items():
        if path[0] == "self_attention":
            close(joyai_tree._get(gp, path[1:]), rp[name])


# -- the expert layer ---------------------------------------------------------

TOK, H, FFN, E, K = 24, 16, 8, 8, 3


def moe(held=None, first=0, axis=None, impl="xla", experts=E, top_k=K,
        **kw):
    cfg = TransformerConfig(
        num_layers=1, hidden_size=H, num_attention_heads=2, vocab_size=8,
        max_position_embeddings=8, ffn_hidden_size=FFN,
        compute_dtype=jnp.float32)
    return MoEMLP(
        config=cfg, num_experts=experts, top_k=top_k, capacity_factor=None,
        expert_axis=axis, activation=jax.nn.silu, router="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=2.5, gated=True,
        shared_experts=1, experts_held=held, first_expert=first, impl=impl,
        **kw)


def moe_params(seed=0, held=E, experts=E):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, *shape: 0.3 * jax.random.normal(k, shape)
    return {"router": n(ks[0], H, experts), "router_bias": n(ks[1], experts),
            "w_in": n(ks[2], held, H, 2 * FFN), "w_out": n(ks[3], held, FFN, H),
            "shared_w_in": n(ks[4], H, 2 * FFN),
            "shared_w_out": n(ks[5], FFN, H)}


def naive_moe(p, x, first=0, held=E, shared=True, top_k=K):
    """One token at a time: choose by s + b, weigh by s, scale, add the
    shared expert."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    silu = lambda a: a / (1 + np.exp(-a))
    ffn = lambda t, w_in, w_out: (
        silu((t @ w_in)[:FFN]) * (t @ w_in)[FFN:]) @ w_out
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        s = 1 / (1 + np.exp(-(x[t] @ p["router"])))
        chosen = np.argsort(-(s + p["router_bias"]), kind="stable")[:top_k]
        gates = s[chosen] / s[chosen].sum() * 2.5
        for e, g in zip(chosen, gates):
            if first <= e < first + held:
                out[t] += g * ffn(x[t], p["w_in"][e - first],
                                  p["w_out"][e - first])
        if shared:
            out[t] += ffn(x[t], p["shared_w_in"], p["shared_w_out"])
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_expert_layer_matches_a_per_token_loop(impl):
    p = moe_params()
    x = jax.random.normal(jax.random.PRNGKey(7), (TOK, H))
    (y, aux), inter = moe(impl=impl).apply(
        {"params": p}, x, mutable=["intermediates"])
    close(y, naive_moe(p, x), 1e-4)
    # the bias moved the choice: plain top-k of s picks otherwise somewhere
    s = jax.nn.sigmoid(x @ p["router"])
    plain = np.sort(np.asarray(jax.lax.top_k(s, K)[1]), -1)
    chosen = np.sort(np.asarray(inter["intermediates"]["moe_chosen"][0]), -1)
    assert (plain != chosen).any()
    assert int(inter["intermediates"]["moe_dropped"][0]) == 0
    assert int(np.sum(inter["intermediates"]["moe_load"][0])) == TOK * K


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_gradient_reaches_router_and_experts_and_not_the_bias(impl):
    p = moe_params(1)
    x = jax.random.normal(jax.random.PRNGKey(8), (TOK, H))
    probe = jax.random.normal(jax.random.PRNGKey(9), (TOK, H))
    g, gx = jax.grad(lambda p, x: jnp.sum(
        moe(impl=impl).apply({"params": p}, x)[0] * probe), (0, 1))(p, x)
    assert float(jnp.max(jnp.abs(g["router_bias"]))) == 0.0
    for name in ("router", "w_in", "w_out", "shared_w_in", "shared_w_out"):
        assert float(jnp.max(jnp.abs(g[name]))) > 1e-4, name
    # against finite differences of the per-token loop, through x
    eps = 1e-4
    for t, c in ((0, 0), (5, 3), (TOK - 1, H - 1)):
        d = np.zeros((TOK, H)); d[t, c] = eps
        num = (np.sum(naive_moe(p, np.asarray(x) + d) * np.asarray(probe))
               - np.sum(naive_moe(p, np.asarray(x) - d) * np.asarray(probe))
               ) / (2 * eps)
        assert abs(float(gx[t, c]) - num) < 2e-3 * max(1.0, abs(num))


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the parts that all the shares give, with the shared
    expert counted once, are the uncut layer."""
    full = moe_params(2)
    x = jax.random.normal(jax.random.PRNGKey(10), (TOK, H))
    whole = moe().apply({"params": full}, x)[0]
    close(whole, naive_moe(full, x), 1e-4)
    shared_only = naive_moe(full, x, held=0)
    parts = []
    for first in range(0, E, 2):
        share = dict(full, w_in=full["w_in"][first:first + 2],
                     w_out=full["w_out"][first:first + 2])
        y = moe(held=2, first=first).apply({"params": share}, x)[0]
        close(y, naive_moe(share, x, first=first, held=2), 1e-4)
        parts.append(np.asarray(y) - shared_only)
    close(sum(parts) + shared_only, whole, 1e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_all_tokens_on_one_held_expert_loses_none(impl):
    """Every token chooses expert 5 (a bias no score can beat): its rows
    fill the buffer far past the mean load, and none is dropped."""
    p = moe_params(3, held=2)
    p["router_bias"] = p["router_bias"].at[5].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(11), (TOK, H))
    (y, _), inter = moe(held=2, first=4, impl=impl).apply(
        {"params": p}, x, mutable=["intermediates"])
    load = np.asarray(inter["intermediates"]["moe_load"][0])
    assert load[1] == TOK and int(inter["intermediates"]["moe_dropped"][0]) == 0
    close(y, naive_moe(p, x, first=4, held=2), 1e-4)


# 2 of 32 experts held, top-4: an even router sends 128 of the 2048
# assignments here, the short buffer holds 512, the worst case 2048
SHARE = dict(held=2, first=6, experts=32, top_k=4)
SHARE_TOK = 512


def _share(seed, crowd):
    """Parameters and tokens of the 2-of-32 share; ``crowd``: every token
    chooses held expert 7 (a bias no score can beat), 512 rows and the
    other's few, which the short buffer cannot hold."""
    p = moe_params(seed, held=2, experts=32)
    if crowd:
        p["router_bias"] = p["router_bias"].at[7].set(10.0)
    return p, jax.random.normal(jax.random.PRNGKey(seed + 20), (SHARE_TOK, H))


@pytest.mark.parametrize("crowd", [False, True])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_short_buffer_and_its_fallback_match_a_per_token_loop(impl, crowd):
    """The counted rows choose the buffer on the device: the short one when
    they fit it, the worst case when they do not; neither loses a row, and
    both are the per-token loop, forward and transposed."""
    p, x = _share(5, crowd)
    layer = moe(impl=impl, **SHARE)
    (y, _), inter = layer.apply({"params": p}, x, mutable=["intermediates"])
    inter = inter["intermediates"]
    want_kw = dict(first=6, held=2, top_k=4)
    close(y, naive_moe(p, x, **want_kw), 1e-4)
    chosen = np.asarray(inter["moe_chosen"][0])
    rows = int(((chosen >= 6) & (chosen < 8)).sum())
    assert (rows > 512) == crowd and rows < SHARE_TOK * 4
    assert bool(inter["moe_compact"][0]) == (not crowd)
    assert int(inter["moe_dropped"][0]) == 0
    assert int(np.sum(inter["moe_load"][0])) == rows
    # gradients: against finite differences of the loop, through x and
    # through one entry each of the experts' and the router's weights
    probe = np.asarray(
        jax.random.normal(jax.random.PRNGKey(9), (SHARE_TOK, H)))
    g, gx = jax.grad(lambda p, x: jnp.sum(
        layer.apply({"params": p}, x)[0] * probe), (0, 1))(p, x)
    assert float(jnp.max(jnp.abs(g["router_bias"]))) == 0.0
    t = int(np.argmax((chosen == 7).any(-1)))   # a token with a row here
    eps = 1e-4

    def loop(p, x):
        return np.sum(naive_moe(p, x, **want_kw) * probe)

    def nudged(name, at, by):
        if name == "x":
            return p, np.asarray(x, np.float64) + by * _one_hot(x.shape, at)
        return dict(p, **{name: np.asarray(p[name], np.float64)
                          + by * _one_hot(p[name].shape, at)}), x

    for name, at in (("x", (t, 0)), ("x", (t, H - 1)), ("w_in", (1, 3, 5)),
                     ("w_out", (1, 2, 7)), ("router", (4, 7))):
        num = (loop(*nudged(name, at, eps)) - loop(*nudged(name, at, -eps))
               ) / (2 * eps)
        got = float((gx if name == "x" else g[name])[at])
        assert abs(num) > 1e-3, (name, at)
        assert abs(got - num) < 2e-3 * max(1.0, abs(num)), (name, at)


def _one_hot(shape, at):
    d = np.zeros(shape)
    d[at] = 1.0
    return d


def test_the_branch_exists_only_where_a_share_is_held():
    """A layer that holds all its experts has the worst-case buffer alone:
    no conditional in its program, forward or backward."""
    def lowered(p, x, **kw):
        return jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(
            moe(**kw).apply({"params": p}, x)[0]))).lower(p, x).as_text()

    branch = ("stablehlo.case", "stablehlo.if", "conditional")
    whole = lowered(moe_params(6, held=32, experts=32),
                    jnp.zeros((SHARE_TOK, H)), experts=32, top_k=4)
    assert not any(b in whole for b in branch)
    share = lowered(*_share(6, False), **SHARE)  # one forward, one backward
    assert share.count("stablehlo.case") + share.count("stablehlo.if") == 2


@pytest.mark.parametrize("devices, experts, top_k, tok, crowd", [
    (4, 8, 3, 24, False),   # the received rows ARE four times the even share
    (8, 16, 2, 64, False),  # 1024 rows can arrive, the short buffer holds 512
    (8, 16, 2, 64, True),   # every token chooses expert 0: rank 0 alone
])                          # takes the worst case, and no collective waits
def test_the_layer_over_an_expert_axis_is_the_local_layer(
        devices, experts, top_k, tok, crowd):
    layer = functools.partial(moe, experts=experts, top_k=top_k)
    p = moe_params(4, held=experts, experts=experts)
    if crowd:
        p["router_bias"] = p["router_bias"].at[0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(12), (devices * tok, H))
    probe = jax.random.normal(jax.random.PRNGKey(13), (devices * tok, H))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]), ("ep",))
    sharded = dict.fromkeys(p, P())
    sharded.update(w_in=P("ep"), w_out=P("ep"))

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(sharded, P("ep"), P("ep")),
        out_specs=(P("ep"), P(), P("ep")), check_vma=False)
    def over_axis(p, x, probe):
        def loss(p, x):
            (y, _), inter = layer(axis="ep").apply(
                {"params": p}, x, mutable=["intermediates"])
            # this rank's tokens' part of the loss: the exchange's own
            # transpose carries the other ranks' cotangents to the experts
            return jnp.sum(y * probe), (
                y, inter["intermediates"]["moe_compact"][0])

        (_, (y, compact)), g = jax.value_and_grad(loss, has_aux=True)(p, x)
        # replicated leaves: every rank holds a partial sum
        g = {k: v if k in ("w_in", "w_out") else jax.lax.psum(v, "ep")
             for k, v in g.items()}
        return y, {k: jax.lax.all_gather(v, "ep", tiled=True)
                   if k in ("w_in", "w_out") else v
                   for k, v in g.items()}, compact[None]

    y, g, compact = over_axis(p, x, probe)
    want, want_g = jax.value_and_grad(
        lambda p: jnp.sum(layer().apply({"params": p}, x)[0] * probe))(p)
    close(y, layer().apply({"params": p}, x)[0], 1e-4)
    for name in p:
        close(g[name], want_g[name], 1e-4)
    short = devices == 8
    assert list(compact) == [short and not (crowd and rank == 0)
                             for rank in range(devices)]


# -- multi-token prediction and the whole model -------------------------------


def _batch(seed=0, rows=2):
    rng = np.random.default_rng(seed)
    t = jnp.asarray(rng.integers(0, 128, (rows, SEQ + 1)).astype(np.int32))
    return t[:, :-1], t[:, 1:]


def test_mtp_loss_and_the_double_gradient_into_embedding_and_head():
    cfg, (tok, lab) = config(), _batch()
    model = GPTModel(config=cfg)
    w = ref.init_weights(ref.seed_key(6), **DIMS)
    p = joyai_tree.to_program(w)
    losses, mtp = model.apply(p, tok, labels=lab)
    assert losses.shape == mtp.shape == (2, SEQ)
    assert float(jnp.max(jnp.abs(mtp[:, -1]))) == 0.0  # no target two ahead
    want, _, _ = ref.loss_and_grads(w, tok, lab, mtp_coeff=0.3, **REF_KW)
    total, main, second = gpt_mtp_loss_fn(losses, mtp, 0.3)
    close([total, main, second], want)

    def grads(coeff):
        g = jax.grad(lambda p: gpt_mtp_loss_fn(
            *model.apply(p, tok, labels=lab), coeff)[0])(p)["params"]
        return (g["embedding"]["word_embeddings"]["embedding"],
                g["output_layer"]["kernel"])

    (e0, h0), (e1, h1) = grads(0.0), grads(1.0)
    # embedding and head are used twice: the second loss adds its own
    # gradient to each, on top of the first's
    for first, both in ((e0, e1), (h0, h1)):
        assert float(jnp.max(jnp.abs(both - first))) > 1e-4
    _, _, g = ref.loss_and_grads(w, tok, lab, mtp_coeff=1.0, **REF_KW)
    close(e1, g["emb"]); close(h1, g["head"])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_model_matches_the_reference_leaf_for_leaf(impl):
    cfg = config(moe_impl=impl, attention_impl=impl)
    tok, lab = _batch(1)
    w = ref.init_weights(ref.seed_key(7), **DIMS)
    losses, chosen, g = ref.loss_and_grads(w, tok, lab, mtp_coeff=0.3,
                                           **REF_KW)
    model = GPTModel(config=cfg)
    value, grads = jax.value_and_grad(lambda p: gpt_mtp_loss_fn(
        *model.apply(p, tok, labels=lab), 0.3)[0])(joyai_tree.to_program(w))
    close(value, losses[0])
    got = joyai_tree.stacked(grads, 3)
    assert sorted(got) == sorted(g)
    for name in g:
        close(got[name], g[name], 1e-5)
    assert chosen.shape == (2, 3, SEQ, 4)


def test_the_model_trains_through_build_gpt_training_like_the_reference():
    from apex_tpu.monitor.metrics import read_bag
    from apex_tpu.training import GPTTargetConfig, build_gpt_training

    sizes, model = described_model(ARCH, layers_kept=3, experts_held=4,
                                   first_expert=4, vocab_rows=128)
    cfg = GPTTargetConfig(**sizes, model=model, seq_len=SEQ, micro_batch=1,
                          global_batch=2, max_devices=1)
    again = GPTTargetConfig.from_json(json.loads(json.dumps(cfg.to_json())))
    assert again == cfg and hash(again) == hash(cfg)
    tr = build_gpt_training(cfg)
    assert tr.num_micro == 2
    w = ref.init_weights(ref.seed_key(8), **DIMS)
    # the step donates its state: a copy, so that ``w`` stays
    params = jax.tree_util.tree_map(jnp.copy, joyai_tree.to_program(w))
    tok, lab = _batch(2)
    state = (params, tr.opt.init(params), tr.scaler.init(),
             tr.sentinel.init())
    out = tr.train_step(*state, tr.init_bag(), *tr.reshape_batch(tok, lab),
                        jnp.float32(0), jnp.float32(1))
    (total, main, second), _, g = ref.loss_and_grads(
        w, tok, lab, mtp_coeff=0.3, **REF_KW)
    bag = read_bag(out[4])
    # bf16 compute against the fp32 reference
    assert abs(float(out[5]) - float(total)) < 2e-3 * float(total)
    assert abs(bag["loss_main"] - float(main)) < 2e-3 * float(main)
    assert abs(bag["loss_mtp"] - float(second)) < 2e-3 * float(second)
    assert bag["moe_dropped"] == 0 and bag["moe_rows_here"] > 0
    # 4 of 16 experts held: four times their even share is every row
    assert bag["moe_compact_share"] == 0.0
    assert 1.0 <= bag["moe_load_max_over_mean"] < 4.0
    m1 = joyai_tree.stacked(out[1].exp_avg, 3)
    want, got = ref.leaf_norms(g), ref.leaf_norms(
        jax.tree_util.tree_map(lambda m: m / 0.1, m1))
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=0.05,
                                   atol=0.05 * float(np.median(want[name])
                                                     ) + 1e-7)
    # the router's bias took no update, weight decay included
    moved = joyai_tree.stacked(out[0], 3)
    assert float(jnp.max(jnp.abs(moved["router_b"] - w["router_b"]))) == 0.0
    assert float(jnp.max(jnp.abs(moved["router"] - w["router"]))) > 0.0


@pytest.mark.parametrize("devices", [1, 2])
def test_the_step_hands_out_its_choices_and_moves_the_bias_by_them(devices):
    """``collect_expert_choices``: the compiled step's own routing, which is
    the reference's but for near-ties; ``moe_bias_update_speed``: the bias
    goes up where an expert took fewer assignments than the mean over the
    whole batch (all chips'), down where more, and by nothing else."""
    from apex_tpu.training import GPTTargetConfig, build_gpt_training

    sizes, model = described_model(
        ARCH, layers_kept=3, experts_held=4, first_expert=4, vocab_rows=128,
        router_bias_update_speed=0.01)
    tr = build_gpt_training(GPTTargetConfig(
        **sizes, model=model, seq_len=SEQ, micro_batch=1, global_batch=2,
        max_devices=devices, collect_expert_choices=True))
    assert (tr.dp, tr.num_micro) == (devices, 2 // devices)
    w = ref.init_weights(ref.seed_key(9), **DIMS)
    params = jax.tree_util.tree_map(jnp.copy, joyai_tree.to_program(w))
    tok, lab = _batch(3)
    out = tr.train_step(params, tr.opt.init(params), tr.scaler.init(),
                        tr.sentinel.init(), tr.init_bag(),
                        *tr.reshape_batch(tok, lab), jnp.float32(0),
                        jnp.float32(1))
    # (dp, microbatches, expert layers, tokens, top_k), one row a microbatch
    got = np.asarray(out[-1])
    assert got.shape == (devices, 2 // devices, 3, SEQ, 4)
    got = got.transpose(1, 0, 2, 3, 4).reshape(2, 3, SEQ, 4)
    _, want, _ = ref.loss_and_grads(w, tok, lab, mtp_coeff=0.3, **REF_KW)
    want = np.asarray(want)
    same = (got[..., :, None] == want[..., None, :]).any(-2)
    assert same[want >= 0].mean() > 0.97      # bf16 against fp32 near-ties
    # the multi-token-prediction block's last position has no target
    counted = np.where(want >= 0, got, -1)
    moved = joyai_tree.stacked(out[0], 3)["router_b"] - w["router_b"]
    close(moved, ref.bias_step(jnp.asarray(counted), 16, 0.01), 1e-6)
    assert {round(float(x), 4) for x in np.unique(moved)} <= {
        -0.01, 0.0, 0.01}
    assert float(jnp.max(jnp.abs(moved))) > 0.0


def test_an_unknown_family_or_setting_is_refused_not_approximated():
    with pytest.raises(ValueError, match="no code for this family"):
        described_model(dict(ARCH, model_type="kimi_linear"))
    with pytest.raises(NotImplementedError, match="n_group"):
        described_model(dict(ARCH, n_group=8))
    sizes, model = described_model(ARCH)
    assert sizes == dict(layers=3, hidden=64, heads=4, vocab=128)
    assert model["mlp_layer_kinds"] == ("dense", "experts", "experts")
    assert model["moe_experts_held"] is None


def test_flops_and_parameter_counts_know_the_new_layer_kinds():
    from apex_tpu import monitor
    from apex_tpu.monitor.xray.hbm.model import (
        TransformerDims, described_param_elements, gpt_param_elements)

    cfg = config()
    variables = jax.eval_shape(
        lambda: GPTModel(config=cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32),
            labels=jnp.zeros((1, SEQ), jnp.int32)))
    leaves = sum(int(np.prod(x.shape))
                 for x in jax.tree_util.tree_leaves(variables))
    assert described_param_elements(cfg) == leaves
    assert gpt_param_elements(TransformerDims.from_config(cfg)) == leaves
    # hand count, h 64, s 32: latent attention's projections and products
    attn = 2 * (64 * 48 + 48 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64
                ) + 2 * 32 * 4 * (24 + 16)
    expert = 3 * 2 * 64 * 32
    dense = attn + 3 * 2 * 64 * 96
    experts = attn + 2 * 64 * 16 + 4 * (4 / 16) * expert + expert
    layer = monitor.transformer_layer_flops_per_token
    assert layer(cfg, 32, 0) == dense and layer(cfg, 32, 1) == experts
    head = 2 * 64 * 128
    assert monitor.gpt_flops_per_token(cfg, 32) == (
        dense + 2 * experts + head + experts + 2 * 2 * 64 * 64 + head)


def test_model_scopes_are_closed_and_the_reader_shows_them():
    from apex_tpu.monitor.goodput import scopes
    from apex_tpu.monitor.xray.timeline.hlo_scopes import (
        classify_path, tiles_of)

    with pytest.raises(ValueError, match="registry is closed"):
        scopes.model_scope("moe_magic")
    assert set(scopes.MODEL_SCOPES) >= {
        "mla_project", "moe_route", "moe_dispatch", "moe_experts",
        "moe_combine", "mtp"}
    wide = scopes.kernel_metadata("flash_fwd", block_q=1024, block_k=1024,
                                  d_qk=192, d_v=128)
    same = scopes.kernel_metadata("flash_fwd", block_q=1024, block_k=1024,
                                  d_qk=64, d_v=64)
    parts = scopes.kernel_metadata("flash_bwd_dq", block_q=1024,
                                   block_k=1024, d_nope=128, d_rope=64,
                                   d_v=128)
    assert tiles_of(wide) == "1024x1024 d192/128"
    assert tiles_of(same) == "1024x1024"
    assert tiles_of(parts) == "1024x1024 d128+64/128"
    assert tiles_of(scopes.kernel_metadata("mla_rope")) == ""
    assert classify_path(
        "jit(train_step)/forward_backward/transformer/layer_3/mlp/"
        "checkpoint/rematted_computation/moe_dispatch/gather") == (
            "forward_backward", "forward",
            "transformer/layer_*/mlp/moe_dispatch")
    # a rule that differentiates its own forward (the expert layer's two
    # buffers) opens its scopes under the transforms
    assert classify_path(
        "jit(train_step)/forward_backward/transpose(forward_backward)/"
        "jvp(GPTModel)/transformer/layer_2/mlp/cond/branch_1_fun/"
        "transpose(jvp(moe_experts))/mul") == (
            "forward_backward", "backward",
            "transformer/layer_*/mlp/moe_experts")
