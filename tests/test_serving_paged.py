"""The serving engine's decode over the KV pool in place.

The engine hands the model's attention layers the PAGED kind of cache (pool
leaves, a block table, per-lane positions: serving/kvcache.py) and the layer
picks its decode branch by that kind. Whatever a model asks of decode
attention in the contiguous branch ``models.generate`` drives (learned or
rope positions, grouped heads, a sliding window) the paged branch gives too:
a mixed batch of ragged requests is served with the tokens and the logits of
the contiguous decode. And the gather of each lane's window cannot come back
unseen: the lowered decode program holds no array that size.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel
from apex_tpu.models.generate import generate
from apex_tpu.serving import ServingConfig, ServingEngine
from apex_tpu.transformer import TransformerConfig

VOCAB = 61
MODELS = {
    "learned": dict(position_embedding_type="learned"),
    "rope-gqa-window": dict(position_embedding_type="rope",
                            num_query_groups=2, attention_window=6),
}
# lanes 3 against four requests: the last one waits for a lane, so lanes
# are reused mid-run, and prompts of 5, 9, 12 and 3 tokens leave the lanes
# at different positions in every tick
PROMPTS, MAX_NEW = (5, 9, 12, 3), (6, 5, 7, 9)
CONFIG = dict(lanes=3, block_size=8, num_blocks=9, max_seq_len=32,
              max_queue_depth=8, collect_logits=True, seed=0)


def _model(kind):
    model = GPTModel(config=TransformerConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4,
        vocab_size=VOCAB, max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, compute_dtype=jnp.float32, **MODELS[kind]))
    variables = model.init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


def _contiguous_decode(model, variables, prompt, max_new):
    """Greedy tokens and each step's next-token logits by the contiguous
    cache: the calls ``models.generate`` makes (a prefill with
    ``cache_len``, then ``decode_step`` a token), unrolled so the logits
    can be kept."""
    total = len(prompt) + max_new
    logits, state = model.apply(variables, jnp.asarray(prompt)[None],
                                cache_len=total, mutable=["cache"])
    rows = [np.asarray(logits[0, -1], np.float32)]
    tokens, cache = [int(rows[-1].argmax())], state["cache"]
    for cur in range(len(prompt), total - 1):
        logits, upd = model.apply(
            {**variables, "cache": cache},
            jnp.asarray([[tokens[-1]]], jnp.int32),
            position_ids=jnp.asarray([[cur]]), cache_len=total,
            decode_step=True, mutable=["cache"])
        cache = upd["cache"]
        rows.append(np.asarray(logits[0, 0], np.float32))
        tokens.append(int(rows[-1].argmax()))
    return tokens, rows


@pytest.fixture(scope="module", params=sorted(MODELS))
def served(request):
    """(model, variables, prompts, the contiguous decode of each, the
    engine's finished requests, the engine)."""
    model, variables = _model(request.param)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, size=n).astype(np.int32)
               for n in PROMPTS]
    # references first: the engine's compile watcher counts every compile
    want = [_contiguous_decode(model, variables, p, m)
            for p, m in zip(prompts, MAX_NEW)]
    eng = ServingEngine(model, variables, ServingConfig(**CONFIG)).start()
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, MAX_NEW)]
    for _ in range(100):
        if eng.idle:
            break
        eng.tick()
    return model, variables, prompts, want, reqs, eng


def test_ragged_requests_get_the_contiguous_decodes_tokens(served):
    model, variables, prompts, want, reqs, eng = served
    assert [r.state for r in reqs] == ["completed"] * len(reqs)
    for req, prompt, m, (tokens, _) in zip(reqs, prompts, MAX_NEW, want):
        assert req.tokens_out == tokens
        # and models.generate itself says the same
        assert req.tokens_out == np.asarray(generate(
            model, variables, jnp.asarray(prompt)[None],
            max_new_tokens=m))[0, len(prompt):].tolist()


def test_ragged_requests_get_the_contiguous_decodes_logits(served):
    """Every step's next-token logits, atol 2e-4: the tolerance the
    serving selftest holds the engine's logits to."""
    *_, want, reqs, eng = served
    for req, (_, rows) in zip(reqs, want):
        assert len(req.logits) == len(rows)
        for got, row in zip(req.logits, rows):
            np.testing.assert_allclose(got, row, atol=2e-4, rtol=0)


def test_the_run_compiled_nothing_and_freed_every_block(served):
    *_, eng = served
    assert eng.steady_state_compiles == 0
    assert eng.allocator.free_blocks == CONFIG["num_blocks"]


def test_decode_keys_read_share_counts_what_the_lanes_held(served):
    """Over the decode ticks, the keys the active lanes' lengths covered
    over lanes x max_seq_len: here every request's positions are known."""
    *_, eng = served
    stats = eng.stats()
    # a request decodes at positions prompt .. prompt + max_new - 2, and a
    # decode step at position p reads p + 1 keys
    keys = sum(sum(range(p + 1, p + m)) for p, m in zip(PROMPTS, MAX_NEW))
    assert eng._decode_keys == keys
    assert stats["decode_keys_read_share"] == pytest.approx(
        keys / (eng._decode_ticks * CONFIG["lanes"] * CONFIG["max_seq_len"]))
    assert 0 < stats["decode_keys_read_share"] < 0.6
    fresh = ServingEngine(*_model("learned"), ServingConfig(**CONFIG))
    assert fresh.stats()["decode_keys_read_share"] is None


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_extract_then_adopt_resumes_a_request_mid_answer(kind):
    """The fleet's hand-off: a request leaves one engine after a few
    decode steps with its blocks' contents and goes on in another, to the
    tokens an uninterrupted decode gives."""
    model, variables = _model(kind)
    prompt = np.random.RandomState(5).randint(
        0, VOCAB, size=11).astype(np.int32)
    tokens, _ = _contiguous_decode(model, variables, prompt, 12)
    cfg = ServingConfig(**dict(CONFIG, collect_logits=False))
    src = ServingEngine(model, variables, cfg).start()
    dst = ServingEngine(model, variables, cfg).start()
    # a bystander on the adopter, so the adopted request lands in another
    # lane and other blocks than it left
    other = dst.submit(prompt[:4], max_new_tokens=20)
    dst.tick()
    # ids are the fleet's to keep apart across engines
    req = src.submit(prompt, max_new_tokens=12, rid=7)
    for _ in range(5):
        src.tick()
    assert req.state == "decode" and 1 < len(req.tokens_out) < 12
    payload = src.extract(req.rid)
    assert payload is not None and src.idle
    assert src.allocator.free_blocks == cfg.num_blocks
    assert dst.adopt(payload)
    for _ in range(40):
        if req.terminal:
            break
        dst.tick()
    assert req.state == "completed" and req.tokens_out == tokens
    assert other.state in ("decode", "completed")


# -- the structure of the compiled decode -----------------------------------


def _array_sizes(text):
    """Element counts of every tensor type in a lowered module's text."""
    sizes = set()
    for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text):
        sizes.add(int(np.prod([int(d) for d in dims[:-1].split("x")])))
    return sizes


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_the_decode_program_holds_no_lanes_window(kind):
    """Cache by copy gathered, a layer, every lane's whole window out of
    the pool: an array of lanes x max_seq_len x h_kv x hd elements (and,
    moved between layouts, further ones of that size). The decode program
    reads the pool where it lies, so its largest arrays are the pool
    leaves and the logits; a lane's window appears nowhere."""
    model, variables = _model(kind)
    # geometry that tells the sizes apart: no weight, pool leaf or logits
    # row of this model has as many elements as a window (checked below)
    cfg = ServingConfig(lanes=3, block_size=8, num_blocks=40,
                        max_seq_len=56, max_queue_depth=4)
    eng = ServingEngine(model, variables, cfg)
    lowered = eng.lower_programs()["decode"]
    tcfg = model.config
    h_kv = tcfg.num_query_groups or tcfg.num_attention_heads
    one_lane = cfg.max_seq_len * h_kv * tcfg.kv_channels
    window = cfg.lanes * one_lane
    pool = cfg.num_blocks * cfg.block_size * h_kv * tcfg.kv_channels
    sizes = _array_sizes(lowered.as_text())
    assert pool in sizes  # the reader of the text sees the pool leaves
    # nothing the size of the lanes' windows, of one lane's window (what a
    # vmapped gather would hold a lane), or of either with a second head
    # axis kept apart (q heads, for grouped kv heads)
    group = tcfg.num_attention_heads // h_kv
    forbidden = {window, one_lane, window * group, one_lane * group}
    honest = {int(x.size) for x in jax.tree_util.tree_leaves(variables)}
    assert not forbidden & (honest | {pool}), "pick another geometry"
    assert not forbidden & sizes, sorted(forbidden & sizes)
    # and by the compiler's own account: the step's temporaries stay
    # under a few blocks a lane, far from a window a lane
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < window * 4, (temp, window * 4)


def test_the_jitted_programs_keep_their_names():
    """The benchmark finds a tick in a device trace as ``jit_decode`` and a
    prompt's pass as ``jit_prefill`` (perf/serve_trace.py), the names jit
    gives the functions ``decode`` and ``prefill``."""
    model, variables = _model("learned")
    eng = ServingEngine(model, variables, ServingConfig(
        lanes=2, block_size=8, num_blocks=8, max_seq_len=32))
    lowered = eng.lower_programs()
    assert "jit_decode" in lowered["decode"].as_text()[:400]
    for bucket in (8, 16, 32):
        assert "jit_prefill" in lowered[bucket].as_text()[:400]
    assert eng._make_decode().__name__ == "decode"
    assert eng._make_prefill(8).__name__ == "prefill"
