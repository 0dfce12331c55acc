"""Fixture driver: a second served model through the one serving window,
made of the hooks ``perf/serve_window.py`` asks of a driver and nothing
else: GPT-2 with rotary positions (``ops/rope.py``: base 10000, halves
rotated, a head's whole width) in place of the learned table. The engine,
the faults planted at its seam and the weights' initialisation are GPT-2's
(``perf/drivers/gpt_serve.py``); the model, its operations and its plain
reference's forward pass are this file's. Written into a fixture root's
``perf/drivers/`` by the tests; never a cell of the benchmark.
"""

import functools
import sys

import jax
import jax.numpy as jnp

from perf import gpt_tree, serve_flops, serve_window
from perf.drivers import gpt_serve as gpt
from perf.reference import gpt as ref
from perf.reference import served

FAULTS, plant_fault, State = gpt.FAULTS, gpt.plant_fault, gpt.State


def weights(st, seed):
    def tree(key):
        w = gpt_tree.to_program(ref.init_weights(key, **st.dims))
        del w["params"]["embedding"]["position_embeddings"]  # no table
        return w
    return jax.jit(tree)(ref.seed_key(seed))


def build(cell, config, seed):
    from apex_tpu.models import GPTModel
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer import TransformerConfig

    st = State()
    st.cell, st.config, st.heads = cell, config, config["n_head"]
    st.dims = dict(layers=config["n_layer"], hidden=config["n_embd"],
                   vocab=config["assumed"]["padded_vocab_size"],
                   max_positions=config["n_positions"])
    st.vocab = st.dims["vocab"]
    st.scfg = ServingConfig(**cell["engine"])
    st.eng = ServingEngine(GPTModel(config=TransformerConfig(
        num_layers=config["n_layer"], hidden_size=config["n_embd"],
        num_attention_heads=config["n_head"], vocab_size=st.vocab,
        max_position_embeddings=config["n_positions"], hidden_dropout=0.0,
        attention_dropout=0.0, position_embedding_type="rope")),
        weights(st, seed), st.scfg).start()
    return st


def request_flops(st, prompt_len, tokens_served):
    # rotary positions add no matrix product: GPT-2's count
    d = st.dims
    return serve_flops.gpt_request_flops(d["layers"], d["hidden"],
                                         d["vocab"], prompt_len,
                                         tokens_served)


def counters(st, stats):
    # what a model adds to the window's counters: here the engine's share of
    # the lanes' positions decode attention read
    return {"decode_keys_read_share": stats["decode_keys_read_share"]}


def _rope(x):
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    lo, hi = jnp.split(x, 2, -1)
    return (x * jnp.cos(angle)
            + jnp.concatenate([-hi, lo], -1) * jnp.sin(angle))


def _block(x, lw, *, heads, precision):
    b, s, h = x.shape
    d = h // heads
    y = ref._layer_norm(x, lw["ln1_g"], lw["ln1_b"])
    qkv = ref._linear(y, lw["qkv_w"], precision) + lw["qkv_b"]
    q, k, v = jnp.split(qkv.reshape(b, s, heads, 3 * d), 3, -1)
    scores = jnp.einsum("bqnd,bknd->bnqk", _rope(q), _rope(k),
                        precision=ref.HIGHEST) / jnp.sqrt(jnp.float32(d))
    future = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(future[None, None], -jnp.inf, scores),
                           -1)
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v, precision=ref.HIGHEST)
    x = x + ref._linear(ctx.reshape(b, s, h), lw["proj_w"], precision) \
        + lw["proj_b"]
    y = ref._layer_norm(x, lw["ln2_g"], lw["ln2_b"])
    y = jax.nn.gelu(ref._linear(y, lw["fc_w"], precision) + lw["fc_b"],
                    approximate=True)
    return x + ref._linear(y, lw["out_w"], precision) + lw["out_b"]


@functools.lru_cache(maxsize=None)
def forward_of(heads):
    def forward(w, tokens, precision):
        block = functools.partial(_block, heads=heads, precision=precision)
        x = jnp.take(w["wte"], tokens, axis=0)
        stacked = {k: w[k] for k in ref.LAYER_LEAVES}
        x, _ = jax.lax.scan(lambda c, lw: (block(c, lw), None), x, stacked)
        x = ref._layer_norm(x, w["lnf_g"], w["lnf_b"])
        return ref._linear(x, w["wte"].T, precision)
    return forward


def replay(st, seed, requests, candidate="served"):
    w = ref.init_weights(ref.seed_key(seed), **st.dims)
    out = [served.served_gaps(
        forward_of(st.heads), w, r["prompt"], r["served"],
        seq=st.cell["engine"]["max_seq_len"],
        rows=st.cell["traffic"]["answer"]["max"], candidate=candidate)
        for r in requests]
    return [g for g, _ in out], [s for _, s in out]


_this = sys.modules[__name__]
window, check, release = (serve_window.window, serve_window.check,
                          serve_window.release)


def setup(cell, config, seed, ctx):
    return serve_window.setup(_this, cell, config, seed, ctx)


def study(cell, config, seeds, ctx, controls=3):
    return serve_window.study(_this, cell, config, seeds, ctx, controls)
