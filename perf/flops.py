"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no later PR can move the numerator of a
utilization. One multiply-add is two operations. Recomputed operations (a
flash backward's second pass over QK^T, activation remat) never count, and
causal attention counts the half of the score matrix it has to compute.
Nothing here imports the program.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind):
    """The chip's published peaks from ``peaks.json``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in perf/peaks.json "
            f"(known: {sorted(table)}): no peak, no utilization")
    return table[device_kind]


# -- GPT ---------------------------------------------------------------------


def gpt_matmul_params(layers, hidden, vocab):
    """Parameters that take part in a matrix multiplication for every token:
    per layer QKV (3h^2), attention output (h^2) and the 4h-wide MLP (8h^2),
    plus the tied output head (vocab*h). Embedding look-ups, biases and norms
    are not matmuls."""
    return layers * 12 * hidden * hidden + vocab * hidden


def gpt_attention_flops_per_token(layers, hidden, keys):
    """Forward score and value matmuls of one query token against ``keys``
    keys: QK^T and PV are 2*keys*hidden operations each, per layer."""
    return layers * 4 * keys * hidden


def gpt_train_flops_per_token(layers, hidden, vocab, seq, causal=True):
    """Forward + backward operations per trained token: 6 per matmul
    parameter, and three times the forward attention, whose mean key count
    under a causal mask is (seq+1)/2."""
    keys = (seq + 1) / 2.0 if causal else float(seq)
    return (6.0 * gpt_matmul_params(layers, hidden, vocab)
            + 3.0 * gpt_attention_flops_per_token(layers, hidden, keys))


def attention_train_cost(batch, heads, seq, head_dim, layers):
    """(operations, bytes) causal attention needs for one training step over
    ``layers`` layers: forward QK^T and PV, backward dV, dP, dQ and dK (six
    matmuls of 2*seq*seq*head_dim, halved under the causal mask; the
    backward's recomputed QK^T is not counted). Bytes: forward reads q, k, v
    and writes o; backward reads q, k, v, o, do and writes dq, dk, dv, all
    bf16."""
    per_matmul = 2.0 * batch * heads * seq * seq * head_dim
    ops = 6.0 * per_matmul * 0.5 * layers
    tensor = batch * heads * seq * head_dim * 2
    return ops, 12.0 * tensor * layers


def roofline_seconds(ops, nbytes, peaks):
    """The least time the chip could take and which bound sets it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
