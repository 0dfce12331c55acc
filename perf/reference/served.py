"""What a served token is held to, for any served model: the gap of the
served token's logit under the reference's best, in units of that
position's logit spread.

The model's own reference supplies one thing, its forward pass
``forward(w, tokens, precision) -> logits``: ``tokens`` (1, seq) int32,
logits (1, seq, vocab) in float32. It computes as the configuration states,
and plainly: float32, ``highest`` matrix precision, no cache, no batching,
one sequence at a time, nothing imported from the program and weights made
from the seed. ``precision`` is ``"f32"`` for the reference itself and
``"fp8"`` for the control (the linear layers' operands rounded to fp8),
which the comparison has to reject. ``forward`` is a static argument of a
jitted program: hand the same object on every call (a module-level
function, or one made once per shape), or each call compiles anew.

What lies here is the model-independent rest: the padding of prompt, served
tokens and rows to one shape for every request, the window of rows of a
request that fills its positions, the gap over the row's spread and whether
the token is the reference's best, and the control's candidate (the token
the fp8 forward puts first, read against the float32 reference in the
served token's stead; it need not decode). Valid for greedy tokens only.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("forward", "rows",
                                             "candidate"))
def _position_gaps(w, tokens, start, served, *, forward, rows, candidate):
    """``tokens`` (seq,): prompt + served tokens, right-padded (the pad is
    causally shadowed). Rows ``start .. start + rows`` of the logits are the
    next-token logits of the served positions. Returns per row the gap of
    the candidate token under the reference's best over the row's spread,
    and whether the candidate is the reference's best."""
    def rows_of(precision):
        logits = forward(w, tokens[None], precision)[0]
        return jax.lax.dynamic_slice_in_dim(logits, start, rows, axis=0)

    ref = rows_of("f32")
    cand = served if candidate == "served" else jnp.argmax(
        rows_of(candidate), axis=-1).astype(served.dtype)
    best = jnp.max(ref, axis=-1)
    picked = jnp.take_along_axis(ref, cand[:, None], axis=-1)[:, 0]
    spread = jnp.std(ref, axis=-1)
    return (best - picked) / spread, cand == jnp.argmax(ref, axis=-1)


def served_gaps(forward, w, prompt, served, *, seq, rows,
                candidate="served"):
    """Per served position of one request: (gap over spread, is the
    reference's best). ``seq`` and ``rows`` pad the sequence and the answer
    to one shape for every request (``seq`` the engine's ``max_seq_len``,
    ``rows`` the longest answer the mix allows), so one program serves the
    whole check."""
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
                                jnp.asarray(padded), forward=forward,
                                rows=rows, candidate=candidate)
    return (np.asarray(gaps)[skip:skip + n],
            np.asarray(same)[skip:skip + n])
