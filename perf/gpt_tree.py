"""The reference's flat, layer-stacked GPT weights <-> the program's tree.

The benchmark makes the weights (``reference/gpt.py:init_weights``) and hands
them to the program in the layout ``apex_tpu.models.GPTModel`` declares; the
same table reads a program-shaped tree (Adam's moments, the parameters) back
into the reference's flat form, for the per-leaf norms it compares.
"""

import jax.numpy as jnp

#: reference leaf -> path inside the program's ``layer_<i>`` subtree
_LAYER = {
    "ln1_g": ("input_layernorm", "scale"),
    "ln1_b": ("input_layernorm", "bias"),
    "qkv_w": ("self_attention", "query_key_value", "kernel"),
    "qkv_b": ("self_attention", "query_key_value", "bias"),
    "proj_w": ("self_attention", "dense", "kernel"),
    "proj_b": ("self_attention", "dense", "bias"),
    "ln2_g": ("post_attention_layernorm", "scale"),
    "ln2_b": ("post_attention_layernorm", "bias"),
    "fc_w": ("mlp", "dense_h_to_4h", "kernel"),
    "fc_b": ("mlp", "dense_h_to_4h", "bias"),
    "out_w": ("mlp", "dense_4h_to_h", "kernel"),
    "out_b": ("mlp", "dense_4h_to_h", "bias"),
}
_GLOBAL = {
    "wte": ("embedding", "word_embeddings", "embedding"),
    "wpe": ("embedding", "position_embeddings"),
    "lnf_g": ("transformer", "final_layernorm", "scale"),
    "lnf_b": ("transformer", "final_layernorm", "bias"),
}


def _put(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def to_program(w):
    """``{"params": ...}`` in the program's layout from stacked weights."""
    layers = w["ln1_g"].shape[0]
    params = {}
    for name, path in _GLOBAL.items():
        _put(params, path, w[name])
    for i in range(layers):
        for name, path in _LAYER.items():
            _put(params, ("transformer", f"layer_{i}") + path, w[name][i])
    return {"params": params}


def stacked(tree, layers):
    """A program-shaped tree as the reference's flat dict of stacked
    leaves (the inverse of ``to_program``)."""
    params = tree["params"]
    out = {name: _get(params, path) for name, path in _GLOBAL.items()}
    for name, path in _LAYER.items():
        out[name] = jnp.stack([
            _get(params, ("transformer", f"layer_{i}") + path)
            for i in range(layers)])
    return out
