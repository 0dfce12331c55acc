"""Operations that serving a request needs, from shapes alone: the serving
side of ``perf/flops.py``, kept with the benchmark for the same reason. One
multiply-add is two operations; causal attention counts the keys a query has
to see; the output head counts only where a token is sampled (a prefill
that computes logits for every prompt position does more than it needs, and
none of that counts). Nothing here imports the program.
"""

from perf import flops


def gpt_prefill_flops(layers, hidden, vocab, prompt_len):
    """A prompt of ``prompt_len`` tokens through the blocks, query p
    against its p keys, and the head once, for the first token."""
    per_token = 2.0 * (flops.gpt_matmul_params(layers, hidden, vocab)
                       - vocab * hidden)
    keys = prompt_len * (prompt_len + 1) / 2.0
    return (per_token * prompt_len + 2.0 * vocab * hidden
            + flops.gpt_attention_flops_per_token(layers, hidden, keys))


def gpt_decode_flops(layers, hidden, vocab, keys):
    """One token through the blocks against a cache of ``keys`` keys (its
    own among them), and the head."""
    return (2.0 * flops.gpt_matmul_params(layers, hidden, vocab)
            + flops.gpt_attention_flops_per_token(layers, hidden, keys))


def gpt_request_flops(layers, hidden, vocab, prompt_len, tokens_out):
    """Everything one request needs for ``tokens_out`` served tokens: the
    prefill (which yields the first) and a decode step for each further
    one, the k-th of which sees ``prompt_len + k - 1`` keys. 0 for no
    token."""
    if tokens_out < 1:
        return 0.0
    steps = tokens_out - 1
    keys = steps * prompt_len + tokens_out * steps / 2.0
    return (gpt_prefill_flops(layers, hidden, vocab, prompt_len)
            + steps * 2.0 * flops.gpt_matmul_params(layers, hidden, vocab)
            + flops.gpt_attention_flops_per_token(layers, hidden, keys))
