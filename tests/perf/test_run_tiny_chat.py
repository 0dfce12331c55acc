"""The serving driver through ``perf/run.py``'s own code, at a tiny fixture
configuration on the CPU (four GPT layers of width 256 with learned positions,
four lanes over a 32-block pool), and the proofs that the output check can
fail: the fp8 control in the engine's place, and the timed path broken
underneath by each fault the cell can have. The fixture cell is registered
from a temporary directory: ``perf/run.py`` is not edited for it.

Nothing here is a device number: the result line says ``cpu``.
"""

import io
import json
import time

import numpy as np
import pytest

from _bench import e2e, fixture_root, layer, load

run = load("run.py", name="perf_test_run_chat")
LIMIT_S = 55.0

GPT_TINY_SERVED = {
    "name": "gpt_tiny", "n_layer": 4, "n_embd": 256, "n_head": 4,
    "n_positions": 64, "vocab_size": 2000,
    "assumed": {"padded_vocab_size": 2048}}

CELL = "gpt_tiny.chat"
CHAT_TINY = {
    "config": "gpt_tiny", "driver": "gpt_serve", "chips": 1,
    "engine": {"lanes": 4, "block_size": 8, "num_blocks": 32,
               "max_seq_len": 64, "max_queue_depth": 16,
               "max_prefills_per_tick": 1},
    "traffic": {"arrivals": "poisson_stratified", "rate_rps": 16.0,
                "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
                "answer": {"median": 14, "sigma": 0.4, "min": 4, "max": 24}},
    "drain_limit_s": 20.0, "check_requests": 12, "study_seconds": 0.6,
    "copy_families": ["copy", "gather", "dynamic-slice", "slice",
                      "transpose"],
    "programs": {"decode": "jit_decode", "prefill": "jit_prefill"},
    "spans": ["submit", "tick", "observe", "wait"], "trace_seconds": 0.4,
    # set as the cell's are (PERF.md 2), from this fixture's own readings on
    # the CPU: the bf16 engine reads at most 0.012 over a dozen seeds, the
    # fp8 control 0.026-0.168 (0.165 on the seed tested), every fault over 0.3
    "limits": {"served_gap_max": 0.02},
}
SERVE_E2E = [e2e("setup_s", "s"), e2e("tpot_p50_ms", "ms")]
SERVE_LAYER = [layer(n, u, m, [CELL]) for n, u, m in (
    ("mfu_pct.serve", "%", "tpot_p50_ms"),
    ("device_idle_pct.serve", "%", "tpot_p50_ms"),
    ("decode_tick_ms.serve", "ms", "tpot_p50_ms"),
    ("decode_copy_share.serve", "share", "tpot_p50_ms"),
    ("tpot_p95_ms.serve", "ms", "tpot_p50_ms"),
    ("prefill_share_pct.serve", "%", "tpot_p50_ms"),
    ("queue_wait_p95_ms.serve", "ms", "tpot_p50_ms"),
    ("ttft_mean_ms.serve", "ms", "tpot_p50_ms"),
    ("ttft_p50_ms.serve", "ms", "tpot_p50_ms"),
    ("ttft_p95_ms.serve", "ms", "tpot_p50_ms"),
    ("loadgen_late_p95_ms.serve", "ms", "tpot_p50_ms"),
    ("kv_pool_peak_share.serve", "share", "tpot_p50_ms"),
    ("kv_pool_held_share.serve", "share", "tpot_p50_ms"))]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture
def chat_root(tmp_path):
    return fixture_root(tmp_path, {CELL: CHAT_TINY},
                        {"gpt_tiny": GPT_TINY_SERVED},
                        SERVE_E2E + SERVE_LAYER)


def _run(root, seed=2**31 + 5, seconds=0.8, trace=0):
    t0 = time.perf_counter()
    out = io.StringIO()
    line = run.run_cell(root, CELL, seed, seconds, trace, allow_cpu=True,
                        out=out)
    took = time.perf_counter() - t0
    assert took < LIMIT_S, f"{CELL} took {took:.0f} s"
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(line))
    keys = list(line)
    assert keys[:5] == KEYS and keys[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    return line


def test_chat_driver_runs_and_is_correct(chat_root):
    line = _run(chat_root)
    assert set(line["metrics"]) == {"setup_s", "tpot_p50_ms"}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] == 13 and line["failed"] == 0
    assert [c["name"] for c in line["compared"]] == [
        "served_gap_max", "requests_never_answered",
        "steady_state_compiles", "compiles_in_window"]


def test_a_traced_chat_run_reports_what_a_cpu_can(chat_root):
    """The host-clock readers and the program's counter report; the readers
    of the device trace find no device plane and stay out of the line,
    never 0; the profiler is stopped at the close, before the drain."""
    line = _run(chat_root, trace=1)
    assert "breakdown" in line
    assert set(line["metrics"]) == {
        "tpot_p95_ms.serve", "queue_wait_p95_ms.serve", "ttft_mean_ms.serve",
        "ttft_p50_ms.serve", "ttft_p95_ms.serve", "loadgen_late_p95_ms.serve",
        "kv_pool_peak_share.serve", "kv_pool_held_share.serve"}
    held = line["metrics"]["kv_pool_held_share.serve"]["value"]
    # what the lanes hold is never more than what they reserved
    assert 0 < held <= line["metrics"]["kv_pool_peak_share.serve"]["value"]
    assert line["metrics"]["kv_pool_peak_share.serve"]["value"] <= 1
    assert line["correct"] is True, line["compared"]


def test_a_traced_chat_run_loads_its_trace_once(chat_root, monkeypatch):
    """The harness loads the ``.xplane.pb`` once and hands the events to the
    correctness check for the time by program and to the reduction; the
    readers read what they read with two loads."""
    from perf import trace_reduce

    calls = []
    for module in (run.load_module(chat_root, "trace_reduce"), trace_reduce):
        def counted(path, real=module.load_xplane):
            calls.append(path)
            return real(path)
        monkeypatch.setattr(module, "load_xplane", counted)
    line = _run(chat_root, seed=2**31 + 9, trace=1)
    assert len(calls) == 1, calls
    assert set(line["metrics"]) == {
        "tpot_p95_ms.serve", "queue_wait_p95_ms.serve", "ttft_mean_ms.serve",
        "ttft_p50_ms.serve", "ttft_p95_ms.serve", "loadgen_late_p95_ms.serve",
        "kv_pool_peak_share.serve", "kv_pool_held_share.serve"}
    assert line["correct"] is True, line["compared"]


# -- the comparison has been shown to fail -----------------------------------


@pytest.mark.parametrize("how", ["stale_position", "block_left_out",
                                 "half_prompt", "token_altered"])
def test_a_broken_engine_reads_incorrect(chat_root, monkeypatch, how):
    """The timed path broken underneath the driver, in the program itself:
    the engine's own compiled programs handed a stale position, a block
    table with a block left out, half a prompt, or with a token altered
    where it is produced."""
    from apex_tpu import serving

    drv = run.load_module(chat_root, "drivers", "gpt_serve")
    real = serving.ServingEngine.start

    def broken(self):
        started = self._started
        real(self)
        if not started:
            drv.plant_fault(self, how, 128)
        return self

    monkeypatch.setattr(serving.ServingEngine, "start", broken)
    line = _run(chat_root)
    assert line["correct"] is False
    over = {c["name"] for c in line["compared"] if c["value"] > c["limit"]}
    assert "served_gap_max" in over, line["compared"]


def test_the_seam_the_faults_are_planted_at_is_still_the_engines():
    """``plant_fault`` swaps the engine's private ``_decode_c`` and
    ``_prefill_c``, and warm-up reads the public config's buckets: a rename
    inside ``serving/engine.py`` has to fail here, loudly, and not leave a
    fault that breaks nothing."""
    from apex_tpu.serving import ServingConfig, ServingEngine

    drv = load("drivers/gpt_serve.py")
    for seam in ("_decode_c", "_prefill_c"):
        assert seam in vars(ServingEngine(None, None, ServingConfig(
            lanes=2, block_size=8, num_blocks=16, max_seq_len=64))), seam
    assert ServingConfig(lanes=2, block_size=8, num_blocks=16,
                         max_seq_len=64).prefill_buckets == (8, 16, 32, 64)

    class Moved:
        config = ServingConfig(lanes=2, block_size=8, num_blocks=16,
                               max_seq_len=64)

    with pytest.raises(AttributeError, match="has moved"):
        drv.plant_fault(Moved(), "stale_position", 128)


def test_an_unanswered_request_reads_incorrect(chat_root, monkeypatch):
    """An answer that comes late is late, not wrong; one that never comes
    is for ``correct``: a drain limit too short for the requests in flight
    at the close (behind an engine slowed to 20 ms a tick) leaves them
    unanswered. The window is long enough that early requests finish
    whatever the CPU's speed (the seed's schedule over 1.2 s: the first
    three, due by 0.09 s, want 12, 10 and 6 tokens), and the last, due at
    1.183 s and wanting 15 tokens, cannot (the close leaves it two ticks)."""
    from apex_tpu import serving

    real = serving.ServingEngine.tick

    def slow_tick(self):
        time.sleep(0.02)
        return real(self)

    monkeypatch.setattr(serving.ServingEngine, "tick", slow_tick)
    cell = dict(CHAT_TINY, drain_limit_s=0.0)
    with open(f"{chat_root}/perf/workloads/{CELL}.json", "w") as f:
        json.dump(cell, f)
    line = _run(chat_root, seconds=1.2)
    never = next(c for c in line["compared"]
                 if c["name"] == "requests_never_answered")
    assert line["attempted"] - line["failed"] >= 1  # some were answered
    assert never["value"] >= 1 and line["failed"] >= never["value"]
    assert line["correct"] is False


def test_study_judges_control_and_faults_through_the_cells_limits(
        chat_root):
    """perf/study.py's readings go through the comparison a run uses, with
    the cell's limits: the program reads correct, the fp8 control and each
    planted fault do not."""
    t0 = time.perf_counter()
    compare = load("compare.py")
    drv = run.load_module(chat_root, "drivers", "gpt_serve")
    ctx = run.Context(chat_root, CHAT_TINY, GPT_TINY_SERVED, 0, 0.0, 0,
                      {"platform": "cpu"}, None)
    verdicts = {}
    for kind, seed, compared, readings in drv.study(
            CHAT_TINY, GPT_TINY_SERVED, [3, 4], ctx, controls=1):
        verdicts.setdefault(kind, []).append(compare.correct(compared))
        assert {c["name"] for c in readings} >= {
            "served_gap_max", "served_gap_p99", "served_gap_mean",
            "served_not_best_share"}
        assert all(c["limit"] == 1e30 for c in readings
                   if c["name"].startswith("served_"))
    assert verdicts == {
        "program": [True, True], "control_fp8": [False],
        "fault_stale_position": [False], "fault_block_left_out": [False],
        "fault_half_prompt": [False], "fault_token_altered": [False]}
    assert time.perf_counter() - t0 < LIMIT_S


# -- the engine against the reference, position by position ------------------


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_prefill_and_paged_decode_agree_with_the_reference(seed):
    """The engine's prefill + paged decode with LEARNED positions against
    the reference's full forward pass, logits and not tokens: the engine
    with ``collect_logits`` hands out each step's next-token logits, and
    every one lies within bf16's rounding of the float32 reference's row
    for the same prompt and served tokens."""
    import dataclasses

    import jax.numpy as jnp

    from apex_tpu.serving import ServingEngine
    from perf.reference import gpt as ref

    drv = load("drivers/gpt_serve.py")
    built = drv.build(CHAT_TINY, GPT_TINY_SERVED, seed)
    eng = ServingEngine(
        built.eng.model, built.eng.variables,
        dataclasses.replace(built.scfg, collect_logits=True)).start()
    rng = np.random.default_rng(seed)
    reqs = [eng.submit(rng.integers(0, 120, size=n, dtype=np.int32), m)
            for n, m in ((5, 6), (17, 12), (33, 9), (40, 16))]
    while not eng.idle:
        eng.tick()
    w = ref.init_weights(ref.seed_key(seed), **built.dims)
    for req in reqs:
        assert req.state == "completed"
        tokens = np.concatenate([req.prompt, req.tokens_out[:-1]])
        want = ref.forward(w, jnp.asarray(tokens)[None], heads=4,
                           precision="f32")[0][len(req.prompt) - 1:]
        got = np.stack(req.logits)
        assert got.shape == want.shape
        # bf16 compute against float32: every logit within a twentieth of
        # the row's spread (this fixture reads 0.01-0.02; a wrong position,
        # a lost block or another token's row reads about 1)
        worst = np.max(np.abs(got - np.asarray(want))) / float(jnp.std(want))
        assert worst < 0.05, worst


def test_the_reference_reads_its_own_best_token_as_no_gap():
    """``served_gaps`` on tokens the reference itself would serve: no gap,
    every token its best; on other tokens the gap is the distance to the
    best in logit spreads, and the fp8 candidate is read in the served
    tokens' stead."""
    import jax.numpy as jnp

    from perf.reference import gpt as ref
    from perf.reference import gpt_serving

    dims = dict(layers=4, hidden=256, vocab=2048, max_positions=64)
    w = ref.init_weights(ref.seed_key(11), **dims)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 2000, size=20, dtype=np.int32)
    served = []
    for _ in range(8):  # the reference decoding greedily, a full pass each
        toks = jnp.asarray(np.concatenate([prompt, served]).astype(np.int32))
        row = ref.forward(w, toks[None], heads=4, precision="f32")[0, -1]
        served.append(int(jnp.argmax(row)))
    kw = dict(heads=4, seq=64, rows=24)
    gaps, same = gpt_serving.served_gaps(w, prompt, served, **kw)
    assert gaps.shape == (8,) and same.all()
    assert np.allclose(gaps, 0.0, atol=1e-6)
    wrong = [(t + 1) % 2048 for t in served]
    gaps, same = gpt_serving.served_gaps(w, prompt, wrong, **kw)
    assert not same[0] and gaps[0] > 1.0  # a random token: spreads below
    fp8, _ = gpt_serving.served_gaps(w, prompt, served, candidate="fp8",
                                     **kw)
    assert fp8.shape == (8,) and np.all(fp8 >= 0)
    with pytest.raises(ValueError, match="does not fit"):
        gpt_serving.served_gaps(w, prompt, [0] * 30, **kw)
    # a request that ends with the sequence: its rows are the window's last
    long = rng.integers(0, 2000, size=56, dtype=np.int32)
    tail, same = gpt_serving.served_gaps(w, long, served, **kw)
    assert tail.shape == (8,)
    toks = jnp.asarray(np.concatenate([long, served[:-1]]).astype(np.int32))
    rows = ref.forward(w, toks[None], heads=4, precision="f32")[0, 55:]
    want = (rows.max(-1) - rows[np.arange(8), np.array(served)]) / rows.std(
        -1)
    assert np.allclose(tail, np.asarray(want), atol=1e-4)
    with pytest.raises(ValueError, match="does not fit"):
        gpt_serving.served_gaps(w, long, [0] * 10, **kw)
