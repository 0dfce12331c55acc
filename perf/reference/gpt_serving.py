"""The serving half of the GPT-2 reference: what a served token is held to.

The reference serves nothing. It runs ``reference/gpt.py``'s full forward
pass once over a request's prompt followed by the tokens the engine served
for it (float32, ``highest`` matrix precision, no cache, no batching, one
sequence at a time), and reads at every served position how far the served
token's logit lies below the reference's best, in units of that position's
logit spread (the standard deviation of the reference's logits over the
vocabulary: with N(0, 0.02) weights about 0.64, so a gap of 0.1 is a sixth
of a standard deviation between the token served and the token the
reference puts first). A greedy engine that computes what the configuration
states serves the reference's best token except at near-ties, where the gap
is a rounding error; a token that came from other weights, another
position, a cache that lost a block or half a prompt lies whole standard
deviations below.

Valid for greedy tokens only. Nothing here imports the program, and the
weights come from ``reference/gpt.py:init_weights`` and the seed.

The control (``candidate="fp8"``): the same forward pass with its linear
layers' operands rounded to fp8 stands in the engine's place. It need not
decode: at each position of the same prompt and tokens, the token IT puts
first is read against the float32 reference in the served token's stead.
"""

import functools

import jax
import jax.numpy as jnp

from perf.reference import gpt


@functools.partial(jax.jit, static_argnames=("heads", "rows", "candidate"))
def _position_gaps(w, tokens, start, served, *, heads, rows, candidate):
    """``tokens`` (seq,): prompt + served tokens, right-padded (the pad is
    causally shadowed). Rows ``start .. start + rows`` of the logits are the
    next-token logits of the served positions. Returns per row the gap of
    the candidate token under the reference's best over the row's spread,
    and whether the candidate is the reference's best."""
    def rows_of(precision):
        logits = gpt.forward(w, tokens[None], heads=heads,
                             precision=precision)[0]
        return jax.lax.dynamic_slice_in_dim(logits, start, rows, axis=0)

    ref = rows_of("f32")
    cand = served if candidate == "served" else jnp.argmax(
        rows_of(candidate), axis=-1).astype(served.dtype)
    best = jnp.max(ref, axis=-1)
    picked = jnp.take_along_axis(ref, cand[:, None], axis=-1)[:, 0]
    spread = jnp.std(ref, axis=-1)
    return (best - picked) / spread, cand == jnp.argmax(ref, axis=-1)


def served_gaps(w, prompt, served, *, heads, seq, rows, candidate="served"):
    """Per served position of one request: (gap over spread, is the
    reference's best). ``seq`` and ``rows`` pad the sequence and the answer
    to one shape for every request (``seq`` the engine's ``max_seq_len``,
    ``rows`` the longest answer the mix allows), so one program serves the
    whole check."""
    import numpy as np

    n, start = len(served), len(prompt) - 1
    if n > rows or start + n > seq:
        raise ValueError(f"prompt {len(prompt)} + answer {n} does not fit "
                         f"({seq} positions, {rows} rows)")
    # a request that fills its positions ends at the sequence's end: its
    # window of ``rows`` rows then starts before its first served position
    first = min(start, seq - rows)
    skip = start - first
    tokens = np.zeros((seq,), np.int32)
    tokens[:len(prompt)] = prompt
    tokens[len(prompt):len(prompt) + n - 1] = served[:-1]
    padded = np.zeros((rows,), np.int32)
    padded[skip:skip + n] = served
    gaps, same = _position_gaps(w, jnp.asarray(tokens), jnp.int32(first),
                                jnp.asarray(padded), heads=heads, rows=rows,
                                candidate=candidate)
    return (np.asarray(gaps)[skip:skip + n],
            np.asarray(same)[skip:skip + n])
