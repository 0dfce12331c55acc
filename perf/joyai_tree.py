"""The reference's flat, layer-stacked JoyAI-LLM-Flash weights <-> the
program's tree.

The benchmark makes the weights (``reference/joyai_llm_flash.py:
init_weights``) and hands them to the program in the layout
``apex_tpu.models.GPTModel`` declares for a described model; the same table
reads a program-shaped tree (Adam's moments, the parameters) back into the
reference's flat form, for the per-leaf norms it compares. The reference
stacks attention leaves over the trunk's layers and then the
multi-token-prediction block's layer, expert leaves over the trunk's expert
layers and then that block's.
"""

import jax.numpy as jnp

_ATTN = {
    "ln1": ("input_layernorm", "scale"),
    "q_a": ("self_attention", "q_a_proj", "kernel"),
    "q_a_ln": ("self_attention", "q_a_layernorm", "scale"),
    "q_b": ("self_attention", "q_b_proj", "kernel"),
    "kv_a": ("self_attention", "kv_a_proj", "kernel"),
    "kv_a_ln": ("self_attention", "kv_a_layernorm", "scale"),
    "kv_b": ("self_attention", "kv_b_proj", "kernel"),
    "o": ("self_attention", "o_proj", "kernel"),
    "ln2": ("post_attention_layernorm", "scale"),
}
_MOE = {
    "router": ("mlp", "router"),
    "router_b": ("mlp", "router_bias"),
    "shared_in": ("mlp", "shared_w_in"),
    "shared_out": ("mlp", "shared_w_out"),
    "w_in": ("mlp", "w_in"),
    "w_out": ("mlp", "w_out"),
}
_GLOBAL = {
    "emb": ("embedding", "word_embeddings", "embedding"),
    "head": ("output_layer", "kernel"),
    "lnf": ("transformer", "final_layernorm", "scale"),
    "mlp_in": ("transformer", "layer_0", "mlp", "dense_h_to_4h", "kernel"),
    "mlp_out": ("transformer", "layer_0", "mlp", "dense_4h_to_h", "kernel"),
    "mtp_hnorm": ("mtp", "hnorm", "scale"),
    "mtp_enorm": ("mtp", "enorm", "scale"),
    "mtp_eh": ("mtp", "eh_proj", "kernel"),
    "mtp_lnf": ("mtp", "final_layernorm", "scale"),
}


def _put(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _blocks(layers):
    """Where each stacked block lives: the trunk's layers, then the MTP
    block's."""
    return [("transformer", f"layer_{i}") for i in range(layers)] + [
        ("mtp", "layer")]


def to_program(w):
    """``{"params": ...}`` in the program's layout from stacked weights."""
    blocks = _blocks(w["ln1"].shape[0] - 1)
    params = {}
    for name, path in _GLOBAL.items():
        _put(params, path, w[name])
    for i, block in enumerate(blocks):
        for name, path in _ATTN.items():
            _put(params, block + path, w[name][i])
    for i, block in enumerate(blocks[1:]):
        for name, path in _MOE.items():
            _put(params, block + path, w[name][i])
    return {"params": params}


def stacked(tree, layers):
    """A program-shaped tree as the reference's flat dict of stacked
    leaves (the inverse of ``to_program``)."""
    params, blocks = tree["params"], _blocks(layers)
    out = {name: _get(params, path) for name, path in _GLOBAL.items()}
    for name, path in _ATTN.items():
        out[name] = jnp.stack([_get(params, b + path) for b in blocks])
    for name, path in _MOE.items():
        out[name] = jnp.stack([_get(params, b + path) for b in blocks[1:]])
    return out
