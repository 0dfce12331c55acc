"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no later PR can move the numerator of a
utilization. One multiply-add is two operations. Recomputed operations (a
flash backward's second pass over QK^T, activation remat) never count, and
causal attention counts the half of the score matrix it has to compute.
Nothing here imports the program.
"""

import fnmatch
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


#: the program's names (``kernel_metadata``) of its flash-attention kernels,
#: as a pattern: ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv`` today.
#: The readers of ALL of attention's kernels go by it, so a backward fused
#: into one kernel stays counted under whatever ``flash_...`` name it takes
FLASH_KERNELS = "flash_*"


def flash_kernel_costs(batch, heads, seq, d_qk, d_v, layers):
    """``{kernel: (operations, bytes)}``: what each of the three flash
    kernels needs for one training step over ``layers`` layers of causal
    attention, q and k ``d_qk`` wide a head, v and the output ``d_v``. The
    six matmuls ``attention_train_cost`` counts, two a kernel, each pair
    one ``d_qk`` and one ``d_v`` wide, halved under the causal mask: the
    forward's QK^T and PV; dq's dP (= dO V^T) and dQ; dk/dv's dV and dK. The
    scores both backward kernels recompute, and the dP the second of them
    computes again, are not counted. Bytes by the tensors a kernel of that
    job reads and writes, bf16 (the rows of lse and delta left out, as
    ``attention_train_cost`` leaves them): forward q, k, v in and o out; dq
    q, k, v, dO in and dq out; dk/dv q, k, v, dO in and dk, dv out. Two
    backward kernels read q, k, v and dO twice, so the three byte counts
    add up to more than ``attention_train_cost``'s, which is one pass's."""
    pair = 2.0 * batch * heads * seq * seq * (d_qk + d_v) * 0.5 * layers
    row = batch * heads * seq * 2.0 * layers  # bytes of one bf16 lane
    return {"flash_fwd": (pair, row * (2 * d_qk + 2 * d_v)),
            "flash_bwd_dq": (pair, row * (3 * d_qk + 2 * d_v)),
            "flash_bwd_dkv": (pair, row * (3 * d_qk + 3 * d_v))}


def attention_shape(config, cell):
    """``(batch, heads, seq, d_qk, d_v, blocks)`` of a cell's flash-
    attention calls, from its configuration's own keys: GPT-2's (``n_head``,
    every head ``n_embd / n_head`` wide) or a latent-attention model's
    (q/k ``qk_nope_head_dim + qk_rope_head_dim``, v ``v_head_dim``, the
    blocks kept plus the multi-token-prediction blocks). None for a
    configuration of neither kind."""
    batch, seq = cell["global_batch"], cell["seq_len"]
    if "n_head" in config:
        d = config["n_embd"] // config["n_head"]
        return batch, config["n_head"], seq, d, d, config["n_layer"]
    if "qk_nope_head_dim" in config:
        return (batch, config["num_attention_heads"], seq,
                config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
                config["v_head_dim"],
                config["layers_kept"] + config["num_nextn_predict_layers"])
    return None


def roofline_seconds(ops, nbytes, peaks):
    """The least time the chip could take and which bound sets it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def roofline_share(reduction, kernels, ops, nbytes, peaks):
    """Some kernels' share of their roofline in percent, from a trace's
    reduction (``trace_reduce.reduce``): the least time for ``ops`` and
    ``nbytes`` a step, times the steps the trace holds, over the device time
    booked to the kernels whose name matches the pattern ``kernels``
    (``fnmatch``: ``flash_fwd``, ``flash_*``; ``kernel_seconds`` goes by the
    name in the kernel's ``kernel_metadata``, whatever its instruction is
    called). None where the trace holds no such kernel, no whole step, or
    there is no chip whose peaks to divide by."""
    if not reduction or peaks is None:
        return None
    secs = sum(s for k, s in reduction["kernel_seconds"].items()
               if fnmatch.fnmatchcase(k, kernels))
    if secs <= 0 or not reduction["steps"]:
        return None
    least, _bound = roofline_seconds(ops, nbytes, peaks)
    # ``kernel_seconds`` are summed over the chips, a step's work is every
    # chip's, and ``steps`` is a chip's count
    return 100.0 * least * reduction["steps"] / secs
