"""Operations and bytes a training step of JoyAI-LLM-Flash's share needs,
from shapes and from the COUNTED assignments that landed on held experts.

Kept with the benchmark, beside ``flops.py`` and under its conventions: one
multiply-add is two operations, nothing recomputed counts, causal attention
counts the half of the score matrix it has to compute. Nothing here imports
the program.
"""


def attention_params(c):
    """Matmul parameters of one latent-attention block a token sees: the
    two low-rank query projections, the joint kv down-projection (with the
    shared rope key), the kv up-projection and the output projection."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (h * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
            + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])
            + heads * c["v_head_dim"] * h)


def expert_params(c):
    """One SwiGLU expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def fixed_matmul_params(c, vocab_rows):
    """Parameters every token multiplies, whatever the router does: the
    attention of every block (trunk + MTP), the dense MLP, each expert
    layer's router (its PUBLISHED width) and shared experts, the MTP
    block's joining projection, and the head twice (trunk and MTP, as
    run)."""
    h = c["hidden_size"]
    expert_layers = c["layers_kept"] - c["first_k_dense_replace"] \
        + c["num_nextn_predict_layers"]
    blocks = c["layers_kept"] + c["num_nextn_predict_layers"]
    per_expert_layer = (h * c["published"]["n_routed_experts"]
                        + c["n_shared_experts"] * expert_params(c))
    return (blocks * attention_params(c)
            + c["first_k_dense_replace"] * 3 * h * c["intermediate_size"]
            + expert_layers * per_expert_layer
            + c["num_nextn_predict_layers"] * 2 * h * h
            + (1 + c["num_nextn_predict_layers"]) * vocab_rows * h)


def attention_train_cost(batch, seq, layers, c):
    """(operations, bytes) causal latent attention needs for one training
    step over ``layers`` blocks: forward QK^T (d_qk wide) and PV (d_v),
    backward dV and dP (d_v), dQ and dK (d_qk): six matmuls of 2*s*s*d a
    head, halved under the causal mask; the backward's recomputed QK^T is
    not counted. Bytes: forward reads q, k (d_qk), v (d_v) and writes o
    (d_v); backward reads q, k, v, o, do and writes dq, dk, dv; all bf16."""
    heads = c["num_attention_heads"]
    d_qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    d_v = c["v_head_dim"]
    ops = 2.0 * batch * heads * seq * seq * 3 * (d_qk + d_v) * 0.5 * layers
    nbytes = batch * heads * seq * 6 * (d_qk + d_v) * 2.0 * layers
    return ops, nbytes


def train_step_flops(c, vocab_rows, batch, seq, rows_on_held_experts):
    """Forward + backward operations of one step: 6 per matmul parameter a
    token sees (the routed experts by the counted rows that landed on held
    experts, all expert layers together), and causal attention."""
    tokens = batch * seq
    blocks = c["layers_kept"] + c["num_nextn_predict_layers"]
    attn, _ = attention_train_cost(batch, seq, blocks, c)
    return (6.0 * fixed_matmul_params(c, vocab_rows) * tokens
            + 6.0 * expert_params(c) * rows_on_held_experts + attn)


def experts_train_cost(c, rows_on_held_experts, expert_layers):
    """(operations, bytes) the routed experts' grouped matmuls need for one
    step, whatever implements them: 6 operations a parameter a row; the
    held experts' weights read in forward and backward and their gradient
    written, and a row's operands (2048 in, 2 x 768 hidden, 768 activated,
    2048 out) moved once forward and twice backward; all bf16."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    ops = 6.0 * expert_params(c) * rows_on_held_experts
    weights = c["n_routed_experts"] * expert_params(c) * 2.0 * 3 \
        * expert_layers
    per_row = (h + 2 * f + f + h) * 2.0 * 3
    return ops, weights + per_row * rows_on_held_experts
