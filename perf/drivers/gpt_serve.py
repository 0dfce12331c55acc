"""Driver: GPT serving through the library's seam, by the one serving window
every served model shares (``perf/serve_window.py``).

The model is ``apex_tpu.models.GPTModel`` from the cell's configuration AS
PUBLISHED (learned positions: ``serve_gpt.build_model`` hard-codes rope, a
model no published config describes, so the driver does not go through it),
in the precisions ``TransformerConfig``'s defaults give (float32
parameters, bf16 compute, bf16 cache), served by ``ServingEngine(model,
variables, ServingConfig(...)).start()``. The weights come from the
benchmark's seed (``perf/reference/gpt.py``), so that the reference can
follow the same run.

This file holds what is GPT-2's alone, the hooks ``serve_window`` asks of a
driver: ``build``, ``weights``, ``request_flops`` (``perf/serve_flops.py``),
``replay`` (``perf/reference/gpt_serving.py``), ``plant_fault`` and
``FAULTS``. ``setup``, ``window``, ``check``, ``release``, ``study``,
``sweep``, ``hlo_text`` and ``scope_names`` are the window's;
``python3 perf/drivers/gpt_serve.py --workload <cell> --sweep ...`` is the
rate sweep that the cell's rate was set from (PERF.md 2).
"""

import os
import sys

import numpy as np

#: the checkout that holds the system under test (this file's own)
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perf import serve_window  # noqa: E402

#: faults ``plant_fault`` knows, each in the timed path itself
FAULTS = ("stale_position", "block_left_out", "half_prompt",
          "token_altered")


class State:
    pass


def weights(st, seed):
    """The program's variables from the seed, on the device in one jitted
    call, float32 as the engine holds them."""
    import jax

    from perf import gpt_tree
    from perf.reference import gpt as ref

    return jax.jit(lambda key: gpt_tree.to_program(
        ref.init_weights(key, **st.dims)))(ref.seed_key(seed))


def build(cell, config, seed):
    """Model, weights of ``seed``, the engine started (every prefill bucket
    and the decode step lowered and compiled: ``ServingEngine.start``)."""
    from apex_tpu.models import GPTModel
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer import TransformerConfig

    st = State()
    st.cell, st.config = cell, config
    e = cell["engine"]
    if e["max_seq_len"] > config["n_positions"]:
        raise ValueError("the engine's max_seq_len passes the published "
                         "position table")
    st.heads = config["n_head"]
    st.dims = dict(layers=config["n_layer"], hidden=config["n_embd"],
                   vocab=config["assumed"]["padded_vocab_size"],
                   max_positions=config["n_positions"])
    st.vocab = st.dims["vocab"]
    tcfg = TransformerConfig(
        num_layers=config["n_layer"], hidden_size=config["n_embd"],
        num_attention_heads=config["n_head"], vocab_size=st.dims["vocab"],
        max_position_embeddings=config["n_positions"],
        hidden_dropout=0.0, attention_dropout=0.0,
        position_embedding_type="learned",
    )
    st.scfg = ServingConfig(**e)  # the cell's keys are the engine's own
    st.eng = ServingEngine(GPTModel(config=tcfg), weights(st, seed),
                           st.scfg).start()
    return st


def request_flops(st, prompt_len, tokens_served):
    from perf import serve_flops

    d = st.dims
    return serve_flops.gpt_request_flops(d["layers"], d["hidden"],
                                         d["vocab"], prompt_len,
                                         tokens_served)


def replay(st, seed, requests, candidate="served"):
    """The reference over each request's prompt and served tokens: per
    request the per-position gaps and whether the token is the reference's
    best (``reference/gpt_serving.py``). ``candidate="fp8"`` reads the
    control's tokens in the served tokens' stead."""
    from perf.reference import gpt as ref
    from perf.reference import gpt_serving

    w = ref.init_weights(ref.seed_key(seed), **st.dims)
    out = [gpt_serving.served_gaps(
        w, r["prompt"], r["served"], heads=st.heads,
        seq=st.cell["engine"]["max_seq_len"],
        rows=st.cell["traffic"]["answer"]["max"], candidate=candidate)
        for r in requests]
    return [g for g, _ in out], [s for _, s in out]


def plant_fault(eng, how, vocab):
    """Break the timed path underneath, for the studies and the tests: the
    engine's own compiled programs, handed tampered arguments or with a
    result altered where it is produced. Returns what undoes it."""
    for seam in ("_decode_c", "_prefill_c"):
        # private names of ``serving/engine.py``: a rename there must fail
        # here, not leave a fault that breaks nothing
        if not hasattr(eng, seam):
            raise AttributeError(f"the engine has no {seam}: the seam the "
                                 "faults are planted at has moved")
    decode, prefills = eng._decode_c, dict(eng._prefill_c)
    nb = eng.config.num_blocks

    def restore():
        eng._decode_c = decode
        eng._prefill_c.update(prefills)

    if how == "stale_position":
        # the decode step reads every lane one position behind
        def stale(pool, variables, tables, positions, *rest):
            return decode(pool, variables, tables,
                          np.maximum(positions - 1, 0), *rest)
        eng._decode_c = stale
    elif how == "block_left_out":
        # the first cache block of every lane never reaches the decode step
        def gapped(pool, variables, tables, *rest):
            tables = np.array(tables)
            tables[:, 0] = nb
            return decode(pool, variables, tables, *rest)
        eng._decode_c = gapped
    elif how == "half_prompt":
        # prefill sees the first half of the prompt, the rest blanked
        def halved(real):
            def prefill(pool, variables, tokens, true_len, *rest):
                tokens = np.array(tokens)
                tokens[int(true_len) // 2:int(true_len)] = 0
                return real(pool, variables, tokens, true_len, *rest)
            return prefill
        for bucket, real in prefills.items():
            eng._prefill_c[bucket] = halved(real)
    elif how == "token_altered":
        # every eighth decode step hands back the next token id
        calls = [0]

        def altered(*args):
            out = decode(*args)
            calls[0] += 1
            if calls[0] % 8:
                return out
            return (out[0], (np.asarray(out[1]) + 1) % vocab) + tuple(out[2:])
        eng._decode_c = altered
    else:
        raise ValueError(f"unknown fault {how!r}")
    return restore


_this = sys.modules[__name__]
window, check, release = (serve_window.window, serve_window.check,
                          serve_window.release)
hlo_text, scope_names = serve_window.hlo_text, serve_window.scope_names


def setup(cell, config, seed, ctx):
    return serve_window.setup(_this, cell, config, seed, ctx)


def study(cell, config, seeds, ctx, controls=3):
    return serve_window.study(_this, cell, config, seeds, ctx, controls)


def sweep(cell, config, seed, rates, seconds, ctx):
    return serve_window.sweep(_this, cell, config, seed, rates, seconds, ctx)


def main(argv=None):
    return serve_window.main(_this, argv)


if __name__ == "__main__":
    sys.exit(main())
