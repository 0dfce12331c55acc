"""Serving-core tests (apex_tpu.serving, docs/serving.md).

Tier-1: the jax-free pieces — the closed request state machine, the
block allocator, the cache-spec bridge, the serving chaos faults, the
Poisson load generator, the taxonomy/router integration, and the
termination-notice latch.

Slow tier: the selftest gate wrapper, the wedged-decode forensic
bundle, and the ACCEPTANCE overload drill — a Poisson burst at >2x the
sustainable rate with slow-decode and client-abandon faults plus a
mid-load SIGTERM, audited from the example's jsonl stream: every
submitted request reaches exactly one terminal state, p99 TTFT of
admitted requests stays inside the configured budget (excess load is
shed, not queued), the drain completes within the grace budget, the
goodput partition identity holds digit-for-digit, and zero post-warmup
recompiles.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from apex_tpu.monitor import MemorySink, MetricRouter, StdoutSink
from apex_tpu.monitor.goodput import accountant, spans
from apex_tpu.resilience.chaos import FaultPlan
from apex_tpu.serving import kvcache, lifecycle
from apex_tpu.serving.loadgen import PoissonLoadGenerator, percentile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- lifecycle state machine ------------------------------------------------


class TestLifecycle:
    def _req(self, **kw):
        kw.setdefault("rid", 0)
        kw.setdefault("prompt", np.array([1, 2, 3], np.int32))
        kw.setdefault("max_new_tokens", 4)
        kw.setdefault("submit_t", 100.0)
        return lifecycle.Request(**kw)

    def test_happy_path_walk(self):
        r = self._req()
        for state in ("queued", "admitted", "prefill", "decode",
                      "completed"):
            lifecycle.transition(r, state, now=101.0)
        assert r.terminal and r.state == "completed"
        assert r.admit_t == 101.0 and r.end_t == 101.0

    def test_machine_is_closed(self):
        r = self._req()
        with pytest.raises(ValueError, match="machine is closed"):
            lifecycle.transition(r, "warp_drive")
        lifecycle.transition(r, "queued")
        # queued cannot jump straight to decode
        with pytest.raises(ValueError, match="illegal transition"):
            lifecycle.transition(r, "decode")

    def test_terminal_states_absorb(self):
        r = self._req()
        lifecycle.transition(r, "rejected", reason="queue_full")
        with pytest.raises(ValueError, match="absorbing"):
            lifecycle.transition(r, "queued")

    def test_every_live_state_can_time_out(self):
        for path in (("queued",), ("queued", "admitted"),
                     ("queued", "admitted", "prefill"),
                     ("queued", "admitted", "prefill", "decode")):
            r = self._req()
            for s in path:
                lifecycle.transition(r, s)
            lifecycle.transition(r, "timed_out", reason="deadline")
            assert r.state == "timed_out"

    def test_record_fields(self):
        mem = MemorySink()
        router = MetricRouter([mem])
        r = self._req(deadline_s=5.0)
        lifecycle.transition(r, "queued", now=100.5)
        lifecycle.emit_request_record(router, 3, r)
        lifecycle.transition(r, "admitted", now=101.0)
        lifecycle.transition(r, "prefill", now=101.2)
        r.first_token_t = 101.5
        lifecycle.transition(r, "completed", now=102.0)
        lifecycle.emit_request_record(router, 7, r)
        router.close()
        first, last = mem.records[0], mem.records[-1]
        assert first["kind"] == "request" and first["state"] == "queued"
        assert "terminal" not in first and first["step"] == 3
        assert last["terminal"] is True
        assert last["queue_wait_s"] == 1.0
        assert last["ttft_s"] == 1.5
        assert last["total_s"] == 2.0
        assert r.expires_at() == 105.0

    def test_no_router_is_noop(self):
        assert lifecycle.emit_request_record(None, 0, self._req()) is None


# -- block allocator --------------------------------------------------------


class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = kvcache.BlockAllocator(8)
        ids = a.alloc(3)
        assert len(set(ids)) == 3 and a.free_blocks == 5
        a.free(ids)
        assert a.free_blocks == 8 and a.used_blocks == 0

    def test_all_or_nothing(self):
        a = kvcache.BlockAllocator(4)
        assert a.alloc(3) is not None
        assert a.alloc(2) is None           # only 1 left: no partial grant
        assert a.free_blocks == 1           # nothing leaked by the refusal
        assert a.alloc(1) is not None

    def test_double_free_refused(self):
        a = kvcache.BlockAllocator(4)
        ids = a.alloc(2)
        a.free(ids)
        with pytest.raises(ValueError, match="not allocated"):
            a.free(ids)

    def test_blocks_needed(self):
        assert kvcache.blocks_needed(1, 16) == 1
        assert kvcache.blocks_needed(16, 16) == 1
        assert kvcache.blocks_needed(17, 16) == 2


# -- cache spec bridge ------------------------------------------------------


class _Leaf:
    def __init__(self, shape, dtype="float32"):
        self.shape, self.dtype = shape, dtype


class TestCacheSpec:
    def _shapes(self):
        return {
            "transformer": {
                "layers_0": {"attention": {
                    "cached_key": _Leaf((1, 4, 32, 8)),
                    "cached_value": _Leaf((1, 4, 32, 8)),
                    "cache_index": _Leaf(()),
                }},
            }
        }

    def test_classify_and_pool_shapes(self):
        spec = kvcache.CacheSpec.from_cache_shapes(self._shapes())
        assert len(spec.kv_leaves) == 2 and len(spec.index_leaves) == 1
        pools = spec.pool_shapes(num_blocks=10, block_size=16)
        for shape, _ in pools.values():
            # a block's token slots as rows, every kv head in the lanes
            assert shape == (10, 16, 4 * 8)

    def test_build_and_extract_roundtrip(self):
        spec = kvcache.CacheSpec.from_cache_shapes(self._shapes())
        pool = {kvcache.CacheSpec.key(l.path): f"arr-{i}"
                for i, l in enumerate(spec.kv_leaves)}
        cache = spec.paged_cache(pool, "tables", "positions")
        att = cache["transformer"]["layers_0"]["attention"]
        # the paged KIND of cache the attention layer picks its branch by
        assert set(att) == {"key_pool", "value_pool", "block_table",
                            "cache_index"}
        assert att["cache_index"] == "positions"
        assert att["block_table"] == "tables"
        assert spec.pool_from_cache(cache) == pool
        # prefill's contiguous kind reads back under the same keys
        contiguous = {"transformer": {"layers_0": {"attention": {
            "cached_key": "k", "cached_value": "v", "cache_index": 3}}}}
        assert spec.kv_from_cache(contiguous) == dict(
            zip(sorted(pool), ("k", "v")))

    def test_refuses_unknown_layouts(self):
        bad = self._shapes()
        bad["transformer"]["layers_0"]["attention"]["prompt_len_local"] = (
            _Leaf(()))
        with pytest.raises(ValueError, match="refuses layouts"):
            kvcache.CacheSpec.from_cache_shapes(bad)
        with pytest.raises(ValueError, match="single-sequence"):
            kvcache.CacheSpec.from_cache_shapes({
                "x": {"cached_key": _Leaf((2, 4, 32, 8)),
                      "cache_index": _Leaf(())},
            })
        with pytest.raises(ValueError, match="no cached_key"):
            kvcache.CacheSpec.from_cache_shapes(
                {"x": {"cache_index": _Leaf(())}})


# -- serving chaos faults ---------------------------------------------------


class TestServingFaults:
    def test_slow_decode_consumed_once(self):
        plan = FaultPlan(slow_decode_steps={3}, slow_decode_s=0.01)
        t0 = time.monotonic()
        assert plan.maybe_slow_decode(3) is True
        assert time.monotonic() - t0 >= 0.01
        assert plan.maybe_slow_decode(3) is False  # consumed
        assert plan.maybe_slow_decode(4) is False

    def test_abandon_and_malformed_ordinals(self):
        plan = FaultPlan(abandon_requests={1}, malformed_requests={2})
        assert not plan.take_abandon(0) and plan.take_abandon(1)
        assert not plan.take_abandon(1)            # consumed
        assert plan.take_malformed(2) and not plan.take_malformed(2)

    def test_burst(self):
        plan = FaultPlan(burst_steps={5}, burst_n=3)
        assert plan.take_burst(4) == 0
        assert plan.take_burst(5) == 3
        assert plan.take_burst(5) == 0             # consumed

    def test_persistent_rearms(self):
        plan = FaultPlan(burst_steps={5}, burst_n=2, persistent=True)
        assert plan.take_burst(5) == 2 and plan.take_burst(5) == 2

    def test_spec_strings_parse(self):
        plan = FaultPlan(slow_decode_steps="3,5-6",
                         abandon_requests="0,2")
        assert plan.slow_decode_steps == frozenset({3, 5, 6})
        assert plan.abandon_requests == frozenset({0, 2})


# -- Poisson load generator -------------------------------------------------


class _FakeEngine:
    """Duck-typed engine: records submissions/cancels, everything
    queues."""

    def __init__(self):
        self.submitted = []
        self.cancelled = []
        self._rid = 0

    def submit(self, prompt, max_new_tokens, temperature=0.0,
               deadline_s=None):
        req = lifecycle.Request(
            rid=self._rid, prompt=np.asarray(prompt),
            max_new_tokens=max_new_tokens, temperature=temperature,
            deadline_s=deadline_s, submit_t=time.monotonic(),
        )
        self._rid += 1
        lifecycle.transition(
            req, "rejected" if req.prompt_len == 0 else "queued",
            reason="malformed" if req.prompt_len == 0 else None,
        )
        self.submitted.append(req)
        return req

    def cancel(self, rid):
        self.cancelled.append(rid)
        return True


class TestPoissonLoadGenerator:
    def test_seeded_schedule_is_deterministic(self):
        a = PoissonLoadGenerator(rate_rps=10, vocab=64, n_requests=5,
                                 seed=3)
        b = PoissonLoadGenerator(rate_rps=10, vocab=64, n_requests=5,
                                 seed=3)
        assert np.array_equal(a._arrivals, b._arrivals)

    def test_pump_submits_due_arrivals(self):
        clock = {"t": 0.0}
        gen = PoissonLoadGenerator(
            rate_rps=100, vocab=64, n_requests=10, seed=0,
            time_fn=lambda: clock["t"])
        eng = _FakeEngine()
        gen.pump(eng)          # anchors t0; nothing due at t=0
        clock["t"] = 1000.0    # everything due
        gen.pump(eng)
        assert gen.done and len(eng.submitted) == 10
        lens = {r.prompt_len for r in eng.submitted}
        assert all(4 <= n <= 24 for n in lens)

    def test_burst_and_malformed_and_abandon(self):
        clock = {"t": 0.0}
        plan = FaultPlan(burst_steps={0}, burst_n=3,
                         malformed_requests={1}, abandon_requests={0})
        gen = PoissonLoadGenerator(
            rate_rps=0.001, vocab=64, n_requests=5, seed=0,
            fault_plan=plan, time_fn=lambda: clock["t"])
        eng = _FakeEngine()
        new = gen.pump(eng)    # no Poisson arrivals due, but the burst
        assert len(new) == 3
        # ordinal 1 (inside the burst) was malformed -> rejected
        assert eng.submitted[1].state == "rejected"
        # ordinal 0 abandon is pending until the NEXT pump
        assert eng.cancelled == []
        gen.pump(eng)
        assert eng.cancelled == [eng.submitted[0].rid]

    def test_percentile_contract(self):
        assert percentile([], 99.0) is None
        assert percentile([1.0], 50.0) == 1.0
        assert percentile([1.0, 3.0], 50.0) == 2.0

    def test_report_math(self):
        gen = PoissonLoadGenerator(rate_rps=1, vocab=8, n_requests=1)
        r = lifecycle.Request(rid=0, prompt=np.array([1], np.int32),
                              max_new_tokens=3, submit_t=10.0)
        lifecycle.transition(r, "queued", now=10.0)
        lifecycle.transition(r, "admitted", now=10.5)
        r.first_token_t = 11.0
        r.tokens_out = [1, 2, 3]
        lifecycle.transition(r, "prefill", now=11.0)
        lifecycle.transition(r, "completed", now=12.0)
        gen.submitted.append(r)
        rep = gen.report()
        assert rep.ttft_s == [1.0]
        assert rep.per_token_s == [0.5]     # (12-11) / (3-1)
        assert rep.summary()["ttft_p50_s"] == 1.0


# -- taxonomy / router integration ------------------------------------------


class TestServingTelemetryIntegration:
    def test_serving_phases_in_closed_taxonomy(self):
        assert {"prefill", "decode", "drain"} <= set(spans.PHASES)
        assert {"prefill", "decode"} <= set(spans.PRODUCTIVE_PHASES)
        assert "drain" in accountant.BADPUT_PHASES
        assert "prefill" not in accountant.BADPUT_PHASES
        # priority: incident > step > prefill > decode > ... > drain
        pri = list(spans.PHASE_PRIORITY)
        assert (pri.index("incident") < pri.index("prefill")
                < pri.index("decode") < pri.index("drain")
                < pri.index("init"))

    def test_stdout_sink_skips_request_kind(self, capsys):
        from apex_tpu.monitor.router import make_record

        sink = StdoutSink()
        sink.emit(make_record("request", 1, id=0, state="queued"))
        sink.emit(make_record("metrics", 1, loss=1.0))
        out = capsys.readouterr().out
        assert "queued" not in out and "step     1" in out

    def test_responder_bundle_extra_merged(self):
        from apex_tpu.resilience.health import IncidentResponder

        r = IncidentResponder(
            10.0, exit_fn=lambda code: None,
            bundle_extra=lambda: {"requests": [{"id": 7}], "queued": 2},
        )
        r._dump({"step": 3, "overdue_s": 1.0, "deadline_s": 10.0})
        assert r.incidents[0]["requests"] == [{"id": 7}]
        assert r.incidents[0]["queued"] == 2

    def test_responder_bundle_extra_failure_isolated(self):
        from apex_tpu.resilience.health import IncidentResponder

        def boom():
            raise RuntimeError("garnish failed")

        r = IncidentResponder(10.0, exit_fn=lambda code: None,
                              bundle_extra=boom)
        r._dump({"step": 3})
        assert len(r.incidents) == 1    # the bundle survived its garnish


class TestTerminationNotice:
    def test_flag_only_latch(self):
        from apex_tpu.utils.autoresume import TerminationNotice

        n = TerminationNotice(install_handlers=False, grace_s=5.0)
        assert not n.signaled and n.grace_deadline() is None
        n.request()
        assert n.signaled
        assert n.grace_deadline() == pytest.approx(
            time.monotonic() + 5.0, abs=0.5)
        n.close()

    def test_real_sigterm_supersedes_router_death_hook(self):
        """The regression shape that wedged the suite: the router
        module's SIGTERM teardown hook flushes and RE-RAISES to die by
        the signal. A TerminationNotice installed over it must observe
        the signal (flag) without chaining into that death — with a
        notice installed, SIGTERM means drain, not die."""
        import apex_tpu.monitor.router as rmod
        from apex_tpu.utils.autoresume import TerminationNotice

        prev = signal.getsignal(signal.SIGTERM)
        prev_installed = rmod._TEARDOWN["installed"]
        try:
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            rmod._TEARDOWN["installed"] = False
            rmod._install_teardown()
            hook = signal.getsignal(signal.SIGTERM)
            assert getattr(hook, "_apex_tpu_router_teardown", False)
            n = TerminationNotice(grace_s=None)
            os.kill(os.getpid(), signal.SIGTERM)
            # the handler runs in the main thread on delivery; being
            # alive to assert IS the point
            for _ in range(100):
                if n.signaled:
                    break
                time.sleep(0.01)
            assert n.signaled and n.grace_deadline() is None
            n.close()
            assert signal.getsignal(signal.SIGTERM) is hook
        finally:
            rmod._TEARDOWN["installed"] = prev_installed
            signal.signal(signal.SIGTERM, prev)


# -- slow tier: the gate, the wedge, and the ACCEPTANCE overload drill ------


def test_serving_selftest_gate():
    """The ``python -m apex_tpu.serving --selftest`` gate exits 0 —
    correctness vs models.generate, admission/shed/deadline/drain, and
    zero post-warmup recompiles on a tiny GPT."""
    from apex_tpu.serving.__main__ import main

    assert main([]) == 0


def test_serving_wedged_decode_bundle():
    """A chaos wedge inside the scheduler loop escalates through the
    incident ladder, and the forensic bundle carries the engine's
    in-flight request table."""
    import jax.numpy as jnp
    import jax

    from apex_tpu.models import GPTModel
    from apex_tpu.resilience.health import IncidentResponder
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer import TransformerConfig

    tcfg = TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4,
        vocab_size=37, max_position_embeddings=0,
        position_embedding_type="rope", hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    model = GPTModel(config=tcfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    mem = MemorySink()
    router = MetricRouter([mem])
    plan = FaultPlan(hang_steps={2}, hang_timeout_s=2.0)
    responder = IncidentResponder(
        0.4, router=router, window=mem, dump_after=2.0, poll_s=0.05,
        exit_fn=lambda code: None,
    )
    cfg = ServingConfig(lanes=2, block_size=8, num_blocks=4,
                        max_seq_len=16, prefill_buckets=(8,), seed=0)
    eng = ServingEngine(model, variables, cfg, router=router,
                        fault_plan=plan, watchdog=responder)
    eng.start()
    responder.bundle_extra = eng.inflight_table
    responder.start()
    try:
        rid = eng.submit(np.array([1, 2, 3], np.int32),
                         max_new_tokens=12).rid
        n = 0
        while not eng.idle and n < 60:
            eng.tick()      # tick 2 wedges for 2 s; dump fires at 0.8 s
            n += 1
    finally:
        responder.stop()
        router.close()
    assert responder.incidents, "the dump level never fired"
    bundle = responder.incidents[0]
    assert bundle["queued"] == 0
    assert [row["id"] for row in bundle["requests"]] == [rid]
    assert bundle["requests"][0]["state"] == "decode"
    # the wedge released; the request still finished (no silent drop)
    assert eng.requests()[0].state == "completed"


def _audit_stream(path):
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    records.append(json.loads(line))
                except ValueError:
                    pass
    return records


def test_serving_overload_drill(tmp_path):
    """ISSUE 13 acceptance: Poisson burst at >2x sustainable with
    slow-decode + client-abandon (+ malformed, + burst) faults and a
    MID-LOAD SIGTERM. From the jsonl stream: every submitted request
    reaches exactly one terminal state, p99 TTFT of admitted requests
    stays within the configured budget (excess SHED, not queued), the
    drain completes within the grace budget, the goodput partition
    identity holds digit-for-digit, and zero post-warmup recompiles."""
    jsonl = str(tmp_path / "serving.jsonl")
    ttft_budget = 2.0
    grace = 60.0
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        APEX_TPU_PREEMPTION_GRACE_S=str(grace),
    )
    args = [
        "x", "--requests", "600", "--rate", "100",
        "--ttft-budget", str(ttft_budget), "--queue-depth", "8",
        "--deadline", "30", "--metrics-jsonl", jsonl,
        "--chaos-slow-decode-steps", "30,60", "--chaos-slow-decode-s",
        "0.3", "--chaos-abandon", "5,15,25",
        "--chaos-malformed", "10,20", "--chaos-burst-steps", "40",
        "--chaos-burst-n", "12",
    ]
    code = (
        "import jax; jax.config.update('jax_platforms','cpu')\n"
        f"import sys; sys.argv={args!r}\n"
        "exec(open('examples/serving/serve_gpt.py').read())\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        # mid-load: wait for real traffic, then deliver the SIGTERM
        t0 = time.monotonic()
        while time.monotonic() - t0 < 300:
            time.sleep(0.5)
            if os.path.exists(jsonl):
                n = sum(1 for r in _audit_stream(jsonl)
                        if r.get("kind") == "request")
                if n > 60:
                    break
        else:
            proc.kill()
            pytest.fail("no serving traffic observed")
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, f"drill rc={proc.returncode}\n{out[-2000:]}"
    assert "termination notice: draining" in out

    records = _audit_stream(jsonl)
    req_records = [r for r in records if r.get("kind") == "request"]
    assert req_records, "no request records in the stream"

    # 1. exactly one terminal state per submitted request — no silent
    # drops, even with abandons, malformed payloads, shed and a drain
    seen = {r["id"] for r in req_records}
    terminal = {}
    for r in req_records:
        if r.get("terminal"):
            terminal.setdefault(r["id"], []).append(r["state"])
    assert set(terminal) == seen
    assert all(len(v) == 1 for v in terminal.values())
    states = {v[0] for v in terminal.values()}
    assert states <= lifecycle.TERMINAL_STATES

    # 2. the overload was real and was SHED with reasons
    reasons = {}
    for r in req_records:
        if r.get("terminal") and r.get("reason"):
            reasons[r["reason"]] = reasons.get(r["reason"], 0) + 1
    assert reasons.get("ttft_budget", 0) + reasons.get("queue_full", 0) \
        > 0, f"nothing shed under >2x load: {reasons}"
    assert reasons.get("malformed", 0) >= 1
    assert reasons.get("client_cancel", 0) >= 1

    # 3. p99 TTFT of ADMITTED requests inside the budget: shedding kept
    # the queue honest instead of letting it grow
    ttfts = [r["ttft_s"] for r in req_records
             if r.get("terminal") and "ttft_s" in r]
    assert ttfts, "no admitted requests measured"
    assert percentile(ttfts, 99.0) <= ttft_budget

    # 4. drain completed within the grace budget
    m = [l for l in out.splitlines() if l.startswith("serving drain:")]
    assert m, f"no drain line in:\n{out[-1500:]}"
    drain_s = float(m[0].split()[2].rstrip("s,"))
    assert drain_s < grace

    # 5. goodput partition identity, digit-for-digit through json
    good = [r for r in records if r.get("kind") == "goodput"]
    assert good, "no goodput summary record"
    g = good[-1]
    total = g["productive_s"]
    for phase in accountant.BADPUT_PHASES:
        total = total + g[f"badput_{phase}_s"]
    assert total + g["unattributed_s"] == g["wall_s"]
    assert g["productive_s"] > 0.0

    # 6. zero post-warmup recompiles in steady state
    assert "steady-state compiles 0" in out
    post_warmup = [r for r in records
                   if r.get("kind") == "compile" and r.get("recompile")]
    assert post_warmup == []


def test_serving_cancel_and_drain_hardening():
    """ISSUE 16 satellites: cancel() from every live state (queued,
    prefill, decode) books exactly one terminal record and reclaims the
    lane/blocks; a SECOND drain returns the first report marked
    ``redundant=True`` and submit-after-drain sheds with a booked
    ``draining`` rejection — records, never exceptions."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTModel
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer import TransformerConfig

    tcfg = TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4,
        vocab_size=37, max_position_embeddings=0,
        position_embedding_type="rope", hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    model = GPTModel(config=tcfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    mem = MemorySink()
    router = MetricRouter([mem])
    cfg = ServingConfig(lanes=2, block_size=8, num_blocks=8,
                        max_seq_len=32, prefill_buckets=(8,), seed=0)
    eng = ServingEngine(model, variables, cfg, router=router)
    eng.start()
    pool = cfg.num_blocks

    # fill both lanes, then a third request has to WAIT in the queue
    a = eng.submit(np.array([1, 2, 3], np.int32), max_new_tokens=8)
    b = eng.submit(np.array([4, 5, 6], np.int32), max_new_tokens=8)
    eng.tick()      # one admission per tick (max_prefills_per_tick=1)
    eng.tick()
    assert a.state == "decode" and b.state == "decode"
    c = eng.submit(np.array([7, 8, 9], np.int32), max_new_tokens=8)
    assert c.state == "queued"

    # 1. cancel from QUEUED: never placed, so the pool is untouched
    free_before = eng.allocator.free_blocks
    assert eng.cancel(c.rid) is True
    assert c.state == "cancelled" and c.reason == "client_cancel"
    assert eng.allocator.free_blocks == free_before
    assert eng.cancel(c.rid) is False     # terminal: cancel is a no-op

    # 2. cancel from DECODE: the lane and its blocks come back
    lane_a, blocks_a = a.lane, a.blocks
    assert eng.cancel(a.rid) is True
    assert a.state == "cancelled"
    assert lane_a not in eng._active
    assert eng.allocator.free_blocks == free_before + len(blocks_a)

    # 3. cancel from PREFILL: the state is intra-tick (admission runs
    # the prefill in the same tick), so build the mid-prefill shape the
    # cancel path must handle — lane and blocks assigned, not yet in a
    # decode lane — and cancel through the engine's one eviction path
    free_mid = eng.allocator.free_blocks
    req = lifecycle.Request(
        rid=997, prompt=np.array([1, 2], np.int32), max_new_tokens=4,
        submit_t=eng.time_fn(),
    )
    for state in ("queued", "admitted", "prefill"):
        lifecycle.transition(req, state, now=eng.time_fn())
    req.lane = eng._free_lane()
    req.blocks = eng.allocator.alloc(2)
    eng._requests[997] = req
    assert eng.cancel(997) is True
    assert req.state == "cancelled"
    assert eng.allocator.free_blocks == free_mid

    n = 0
    while not eng.idle and n < 60:
        eng.tick()
        n += 1
    assert b.state == "completed"
    assert eng.allocator.free_blocks == pool

    # 4. drain re-entrancy: the second call replays the first report
    first = eng.drain(grace_s=5.0)
    assert "redundant" not in first
    second = eng.drain()
    assert second["redundant"] is True
    assert second["finished"] == first["finished"]
    assert second["evicted"] == first["evicted"]

    # 5. submit-after-drain: a booked rejection, never an exception
    late = eng.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4)
    assert late.terminal and late.state == "rejected"
    assert late.reason == "draining"
    router.close()

    # every id that ever appeared reached EXACTLY one terminal record
    terminal = {}
    for r in mem.snapshot():
        if r.get("kind") == "request" and r.get("terminal"):
            terminal.setdefault(r["id"], []).append(r["state"])
    assert set(terminal) == {a.rid, b.rid, c.rid, 997, late.rid}
    assert all(len(v) == 1 for v in terminal.values())


# -- one decode step in flight across ticks (engine.py) ---------------------


@pytest.fixture(scope="module")
def tiny_gpt():
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTModel
    from apex_tpu.transformer import TransformerConfig

    model = GPTModel(config=TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4, vocab_size=37,
        max_position_embeddings=0, position_embedding_type="rope",
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.float32,
    ))
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    return model, variables


@pytest.fixture(scope="module")
def greedy(tiny_gpt):
    """Step-by-step greedy reference over the contiguous cache: a prefill,
    then one ``decode_step`` call a token, each token read before the next
    step is made (jitted, the cache at the engines' ``max_seq_len``, so
    one decode program serves every reference)."""
    import jax
    import jax.numpy as jnp

    model, variables = tiny_gpt
    cache_len = 32
    prefill = jax.jit(lambda v, p: model.apply(
        v, p, cache_len=cache_len, mutable=["cache"]))
    step = jax.jit(lambda v, c, tok, pos: model.apply(
        {**v, "cache": c}, tok, position_ids=pos, cache_len=cache_len,
        decode_step=True, mutable=["cache"]))

    def run(prompt, max_new):
        logits, state = prefill(variables, jnp.asarray(prompt)[None])
        tokens = [int(np.asarray(logits[0, -1]).argmax())]
        cache = state["cache"]
        for cur in range(len(prompt), len(prompt) + max_new - 1):
            logits, upd = step(variables, cache,
                               jnp.asarray([[tokens[-1]]], jnp.int32),
                               jnp.asarray([[cur]], jnp.int32))
            cache = upd["cache"]
            tokens.append(int(np.asarray(logits[0, 0]).argmax()))
        return tokens

    return run


def _prompts(*lens, seed=11):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 37, size=n).astype(np.int32) for n in lens]


def _engine(tiny_gpt, **kw):
    from apex_tpu.serving import ServingConfig, ServingEngine

    time_fn = kw.pop("time_fn", time.monotonic)
    cfg = dict(lanes=3, block_size=8, num_blocks=12, max_seq_len=32,
               prefill_buckets=(8, 16), max_queue_depth=8, seed=0)
    cfg.update(kw)
    return ServingEngine(*tiny_gpt, ServingConfig(**cfg),
                         time_fn=time_fn).start()


def _run_to_idle(eng, limit=200):
    for _ in range(limit):
        if eng.idle:
            return
        eng.tick()
    raise AssertionError("the engine never went idle")


# (prompt length, max_new_tokens, tick it is submitted before): mixed
# lengths through both buckets, answers of 1 and 2 tokens, and requests
# that arrive while a decode step is in flight, into lanes freed mid-run
AHEAD_CASES = {
    "mixed_lengths": [(5, 7, 0), (13, 4, 0), (3, 9, 0)],
    "admitted_in_flight": [(6, 8, 0), (11, 6, 2), (4, 5, 3), (9, 7, 5),
                           (2, 6, 9)],
    "one_and_two_tokens": [(7, 1, 0), (5, 2, 0), (12, 2, 1), (3, 1, 1),
                           (8, 6, 2), (4, 2, 4)],
}


@pytest.mark.parametrize("case", sorted(AHEAD_CASES))
def test_dispatched_ahead_serves_the_step_by_step_tokens(tiny_gpt, greedy,
                                                       case):
    """Step t runs on step t-1's tokens where they lie while the host
    reads step t-1: the tokens served are the reference's, one request at
    a time, token for token."""
    plan = AHEAD_CASES[case]
    prompts = _prompts(*(n for n, _, _ in plan))
    want = [greedy(p, m) for p, (_, m, _) in zip(prompts, plan)]
    eng = _engine(tiny_gpt)
    reqs, tick = [None] * len(plan), 0
    while any(r is None for r in reqs) or not eng.idle:
        for i, (_, m, at) in enumerate(plan):
            if reqs[i] is None and at <= tick:
                reqs[i] = eng.submit(prompts[i], max_new_tokens=m)
        eng.tick()
        tick += 1
        assert tick < 200
    assert [r.state for r in reqs] == ["completed"] * len(plan)
    assert [r.tokens_out for r in reqs] == want
    assert eng.allocator.free_blocks == eng.config.num_blocks
    assert eng.steady_state_compiles == 0


def test_dispatched_ahead_compiles_nothing_after_start(tiny_gpt):
    """Admissions into a batch with a step in flight, a cancel and an
    extract read mid-flight: every program the tick runs was compiled in
    start()."""
    prompts = _prompts(5, 12, 3, 9)
    eng = _engine(tiny_gpt)
    a = eng.submit(prompts[0], max_new_tokens=9)
    eng.tick()
    b = eng.submit(prompts[1], max_new_tokens=6)
    eng.tick()
    eng.cancel(a.rid)
    c = eng.submit(prompts[2], max_new_tokens=5)
    eng.tick()
    eng.tick()
    assert eng.extract(b.rid) is not None
    eng.submit(prompts[3], max_new_tokens=4)
    _run_to_idle(eng)
    assert c.state == "completed"
    assert eng.steady_state_compiles == 0
    assert eng.stats()["steady_state_compiles"] == 0


def test_cancel_with_a_step_in_flight_drops_its_token(tiny_gpt, greedy):
    """The in-flight token of a cancelled request is dropped, its lane and
    blocks go to the next request in the same tick (the device orders that
    prefill after the step in flight), and that request's tokens are
    right."""
    pa, pb = _prompts(6, 7)
    want_b = greedy(pb, 9)
    # one lane and blocks for one request: B can only take A's
    eng = _engine(tiny_gpt, lanes=1, num_blocks=3, prefill_buckets=(8,),
                  max_seq_len=24)
    a = eng.submit(pa, max_new_tokens=12)
    eng.tick()
    eng.tick()
    assert eng._inflight is not None and len(a.tokens_out) == 2
    lane, blocks = a.lane, set(a.blocks)
    b = eng.submit(pb, max_new_tokens=9)
    assert b.state == "queued"
    assert eng.cancel(a.rid)
    assert a.state == "cancelled" and len(a.tokens_out) == 2
    eng.tick()      # B admitted into A's lane and blocks; A's token read
    assert (b.state, b.lane) == ("decode", lane)
    assert set(b.blocks) <= blocks
    assert len(a.tokens_out) == 2
    _run_to_idle(eng)
    assert b.state == "completed" and b.tokens_out == want_b
    assert len(a.tokens_out) == 2
    assert eng.allocator.free_blocks == 3


def test_deadline_with_a_step_in_flight_drops_its_token(tiny_gpt, greedy):
    clock = {"t": 100.0}
    pa, pb = _prompts(5, 9)
    want_b = greedy(pb, 6)
    eng = _engine(tiny_gpt, time_fn=lambda: clock["t"])
    a = eng.submit(pa, max_new_tokens=10, deadline_s=1.0)
    b = eng.submit(pb, max_new_tokens=6)
    eng.tick()      # A prefilled, its first decode step dispatched
    eng.tick()      # B prefilled, both step; A's first step read
    assert eng._inflight is not None and len(a.tokens_out) == 2
    clock["t"] += 2.0
    eng.tick()      # the sweep times A out before its step is read
    assert (a.state, a.reason) == ("timed_out", "deadline")
    assert len(a.tokens_out) == 2
    assert a.lane not in eng._active
    _run_to_idle(eng)
    assert len(a.tokens_out) == 2
    assert b.state == "completed" and b.tokens_out == want_b
    assert eng.allocator.free_blocks == eng.config.num_blocks


def test_idle_and_drain_with_a_step_in_flight(tiny_gpt, greedy):
    """``idle`` is false while a step is in flight, even with no request
    left to serve; ``drain`` reads the step, finishing what it carries."""
    pa, pb, pc = _prompts(5, 8, 4)
    want_b = greedy(pb, 5)
    eng = _engine(tiny_gpt)
    a = eng.submit(pa, max_new_tokens=6)
    eng.tick()
    eng.cancel(a.rid)
    assert not eng._queue and not eng._active
    assert not eng.idle         # A's step is still in flight
    eng.tick()                  # read, its token dropped
    assert eng.idle and len(a.tokens_out) == 1

    b = eng.submit(pb, max_new_tokens=5)
    eng.tick()
    eng.tick()
    assert not eng.idle and eng._inflight is not None
    report = eng.drain(grace_s=60.0)
    assert report["finished"] == 1 and report["evicted"] == 0
    assert b.state == "completed" and b.tokens_out == want_b
    assert eng.idle and eng._inflight is None

    # a zero-grace drain evicts, and the step in flight is read with it
    eng2 = _engine(tiny_gpt)
    c = eng2.submit(pc, max_new_tokens=8)
    eng2.tick()
    assert eng2._inflight is not None
    report2 = eng2.drain(grace_s=0.0)
    assert report2["evicted"] == 1
    assert (c.state, c.reason) == ("timed_out", "drain_deadline")
    assert len(c.tokens_out) == 1
    assert eng2.idle and eng2.allocator.free_blocks == 12


def test_decode_dispatched_ahead_share(tiny_gpt):
    """A step counts as dispatched ahead when the last one's tokens were
    still unread: not the first step after an empty engine, nor the step
    of a tick that admitted a request (its first token is merged with the
    others' on the host)."""
    pa, pb = _prompts(5, 6)
    eng = _engine(tiny_gpt)
    assert eng.stats()["decode_dispatched_ahead_share"] is None
    a = eng.submit(pa, max_new_tokens=6)    # 5 decode steps
    _run_to_idle(eng)
    assert a.state == "completed"
    assert eng.stats()["decode_dispatched_ahead_share"] == pytest.approx(
        4 / 5)
    a = eng.submit(pa, max_new_tokens=7)    # steps 6-11
    eng.tick()
    eng.tick()
    b = eng.submit(pb, max_new_tokens=3)    # joins at step 8, merged
    _run_to_idle(eng)
    assert a.state == b.state == "completed"
    # 11 steps: the two after an empty engine and the one B joined are
    # not ahead
    assert eng.stats()["decode_dispatched_ahead_share"] == pytest.approx(
        8 / 11)


# -- the host's account of a tick (engine.py) --------------------------------


def _ticks(eng, limit=200):
    """Tick to idle; each tick's (start, end) by ``perf_counter_ns``, the
    clock the account stamps its parts with."""
    spans = []
    while not eng.idle:
        before = time.perf_counter_ns()
        eng.tick()
        spans.append((before, time.perf_counter_ns()))
        assert len(spans) < limit
    return spans


def _parts(log):
    from apex_tpu.serving.engine import TICK_PARTS

    return np.stack([log[p + "_ns"] for p in TICK_PARTS], axis=1)


def test_a_ticks_parts_add_up_to_its_wall_time(tiny_gpt):
    """Every logged tick's five parts are its wall time, stamped inside the
    caller's own stamps around ``tick()``; the lanes, prefills and steps
    dispatched ahead are the tick's."""
    pa, pb = _prompts(5, 11)
    eng = _engine(tiny_gpt)
    eng.submit(pa, max_new_tokens=6)
    eng.submit(pb, max_new_tokens=4)
    spans = _ticks(eng)
    log = eng.tick_log()
    parts = _parts(log)
    assert len(parts) == len(spans) == eng.stats()["ticks"]
    assert (parts >= 0).all()
    for (before, after), start, total in zip(spans, log["start_ns"],
                                             parts.sum(axis=1)):
        assert before <= start and start + total <= after
        assert total > 0
    # two admissions, one a tick; B joins A's step at tick 1, then both
    # step ahead until B's last token (step 3) and A's (step 4); tick 5
    # only reads
    assert log["prefills"].tolist() == [1, 1, 0, 0, 0, 0]
    assert log["lanes"].tolist() == [1, 2, 2, 2, 1, 0]
    assert log["ahead"].tolist() == [False, False, True, True, True, False]


class _SlowTokens:
    """A decode step's tokens whose fetch takes ``delay`` seconds: a step
    the device is still running when the host asks for it."""

    def __init__(self, real, delay):
        self.real, self.delay = real, delay

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay)
        return np.asarray(self.real, dtype)


def test_a_slow_device_step_lands_in_wait_not_dispatch(tiny_gpt, greedy):
    """A stub compiled step whose token fetch sleeps: the sleep is booked
    where the host blocks on the step (reading its tokens, or merging a
    fresh lane's), never in the call that dispatched it."""
    delay = 0.05
    pa, pb = _prompts(6, 9)
    eng = _engine(tiny_gpt)
    real = eng._decode_c

    def slow(pool, variables, tables, positions, tokens, *rest):
        if isinstance(tokens, _SlowTokens):
            tokens = tokens.real
        out = real(pool, variables, tables, positions, tokens, *rest)
        return (out[0], _SlowTokens(out[1], delay)) + tuple(out[2:])

    eng._decode_c = slow
    a = eng.submit(pa, max_new_tokens=5)
    eng.tick()
    b = eng.submit(pb, max_new_tokens=3)   # merged with A's step in flight
    _ticks(eng)
    assert a.tokens_out == greedy(pa, 5) and b.tokens_out == greedy(pb, 3)
    parts = _parts(eng.tick_log())
    from apex_tpu.serving.engine import TICK_PARTS

    wait, dispatch = (parts[:, TICK_PARTS.index(p)]
                      for p in ("wait", "dispatch"))
    # every tick after the first reads (or merges) a step in flight
    assert (wait[1:] >= delay * 1e9).all() and wait[0] < delay * 1e9
    assert (dispatch < delay * 1e9).all()


def test_a_tick_that_admits_books_its_prefill(tiny_gpt):
    """The prefill call through its first token is ``prefill``, not
    ``admit``: a prefill slowed by a sleep shows there alone."""
    delay = 0.05
    (pa,) = _prompts(5)
    eng = _engine(tiny_gpt)
    real = eng._prefill_c[8]

    def slow(*args):
        time.sleep(delay)
        return real(*args)

    eng._prefill_c[8] = slow
    a = eng.submit(pa, max_new_tokens=3)
    _ticks(eng)
    assert a.state == "completed"
    log = eng.tick_log()
    assert log["prefills"].tolist() == [1, 0, 0]
    assert log["prefill_ns"][0] >= delay * 1e9
    assert log["admit_ns"][0] < delay * 1e9
    assert (log["prefill_ns"][1:] == 0).all()


def test_the_tick_log_keeps_the_newest_ticks_when_it_wraps(tiny_gpt,
                                                          monkeypatch):
    from apex_tpu.serving import engine as engine_mod

    monkeypatch.setattr(engine_mod, "TICK_LOG_TICKS", 4)
    (pa,) = _prompts(7)
    eng = _engine(tiny_gpt)
    eng.submit(pa, max_new_tokens=9)
    spans = _ticks(eng)
    assert len(spans) == 9   # a prefill and 8 steps, the last read alone
    log = eng.tick_log()
    assert {len(v) for v in log.values()} == {4}
    for (before, after), start in zip(spans[-4:], log["start_ns"]):
        assert before <= start <= after
    assert log["lanes"].tolist() == [1, 1, 1, 0]


def test_host_tick_ms_is_none_before_the_first_tick(tiny_gpt):
    """``stats()["host_tick_ms"]``: nothing before a tick, then the median
    of every part and of the whole tick over the ring."""
    from apex_tpu.serving.engine import TICK_PARTS

    (pa,) = _prompts(5)
    eng = _engine(tiny_gpt)
    assert eng.stats()["host_tick_ms"] is None
    assert {len(v) for v in eng.tick_log().values()} == {0}
    eng.submit(pa, max_new_tokens=4)
    _ticks(eng)
    got = eng.stats()["host_tick_ms"]
    assert set(got) == set(TICK_PARTS) | {"tick"}
    ms = _parts(eng.tick_log()) / 1e6
    assert got["tick"] == pytest.approx(float(np.median(ms.sum(axis=1))))
    assert got["dispatch"] == pytest.approx(
        float(np.median(ms[:, TICK_PARTS.index("dispatch")])))


def test_each_part_opens_its_annotation_while_a_session_records(
        tiny_gpt, monkeypatch):
    """While a profiler session records, each part is an ``engine.<part>``
    annotation, nested so that the innermost one at any instant is the
    part the time is booked to; with none recording, none is made."""
    from apex_tpu.serving import engine as engine_mod

    events = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append("+" + self.name)

        def __exit__(self, *exc):
            events.append("-" + self.name)

    recording = type("Profiler", (), {"TraceAnnotation": Annotation})
    pa, pb = _prompts(5, 9)
    eng = _engine(tiny_gpt)
    eng.submit(pa, max_new_tokens=6)
    eng.tick()
    eng.tick()
    assert events == []
    monkeypatch.setattr(engine_mod, "profiling", lambda: recording)
    eng.tick()                          # steady: dispatch ahead, then read
    assert events == [
        "+engine.admit", "-engine.admit",
        "+engine.book", "+engine.dispatch", "-engine.dispatch",
        "+engine.wait", "-engine.wait", "-engine.book",
        "+engine.book", "-engine.book"]
    del events[:]
    eng.submit(pb, max_new_tokens=2)
    eng.tick()                          # admits: B's prefill, merged step
    assert events == [
        "+engine.admit", "+engine.prefill", "-engine.prefill",
        "-engine.admit",
        "+engine.book", "+engine.wait", "-engine.wait",
        "+engine.dispatch", "-engine.dispatch",
        "+engine.wait", "-engine.wait", "-engine.book",
        "+engine.book", "-engine.book"]
