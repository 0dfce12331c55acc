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

The arithmetic of the gap, the padding and the fp8 control's candidate is
every served model's (``reference/served.py``); what is GPT-2's here is the
forward pass it is handed. Valid for greedy tokens only. Nothing here
imports the program, and the weights come from ``reference/gpt.py:
init_weights`` and the seed.
"""

import functools

from perf.reference import gpt
from perf.reference import served as shared


@functools.lru_cache(maxsize=None)
def forward_of(heads):
    """``forward(w, tokens, precision) -> logits`` of a GPT-2 of ``heads``
    heads, one object a head count: the shared program takes it as a static
    argument and compiles once for it."""
    def forward(w, tokens, precision):
        return gpt.forward(w, tokens, heads=heads, precision=precision)
    return forward


def served_gaps(w, prompt, served, *, heads, seq, rows,
                candidate="served"):
    """Per served position of one request: (gap over spread, is the
    reference's best), by ``reference/served.py:served_gaps`` with GPT-2's
    forward pass. ``candidate="fp8"`` reads the token the fp8 forward puts
    first in the served token's stead."""
    return shared.served_gaps(forward_of(heads), w, prompt, served,
                              seq=seq, rows=rows, candidate=candidate)
