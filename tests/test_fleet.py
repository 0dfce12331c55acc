"""Serving-fleet tests (apex_tpu.serving.fleet, docs/serving.md "Fleet").

Tier-1: the jax-free pieces — the shared-prefix radix index, the KV
handoff ledger, the two-sided debounced autoscaler, the replica health
machine (detect -> restart -> probation -> readmit on the PR-15 policy
table, escalation on exhausted budgets), and fleet-config validation.

Slow tier: the ``--selftest --fleet`` gate wrapper and the ACCEPTANCE
chaos drill — a seeded Poisson load over a disaggregated 3-replica
fleet with a mid-load replica kill: failover re-dispatches the dead
replica's in-flight work, an SLO breach scales the fleet up, p99 TTFT
stays inside the drill budget, every global id reaches exactly one
terminal record (zero silent drops), the handoff ledger closes matched,
and the goodput partition identity holds digit-for-digit fleet-wide.
"""

import numpy as np
import pytest

from apex_tpu.resilience.remediation.policy import (
    TERMINAL_VERDICTS,
    RemediationPolicy,
)
from apex_tpu.serving import lifecycle
from apex_tpu.serving.fleet import (
    FleetAutoscaler,
    FleetConfig,
    HandoffLedger,
    RadixPrefixIndex,
    Replica,
)
from apex_tpu.serving.loadgen import percentile


class _CapRouter:
    """MetricRouter.event-shaped capture: enough surface for the
    jax-free fleet pieces, zero sink machinery."""

    def __init__(self):
        self.records = []

    def event(self, kind, step, **fields):
        rec = {"kind": kind, "step": int(step), **fields}
        self.records.append(rec)
        return rec


# -- shared-prefix radix index ----------------------------------------------


class TestRadixPrefixIndex:
    def test_longest_indexed_prefix_wins(self):
        idx = RadixPrefixIndex(block_size=4)
        toks = list(range(12))
        assert idx.insert(toks[:8], "a") == 2
        # same 8 tokens: full hit at block granularity
        assert idx.lookup(toks[:8]) == ("a", 8)
        # shared 8-token prefix plus a novel tail: the hit is the
        # longest indexed prefix, not all-or-nothing
        assert idx.lookup(toks[:8] + [99, 98, 97, 96]) == ("a", 8)
        s = idx.stats()
        assert s["hits"] == 2 and s["lookups"] == 2
        assert s["hit_tokens"] == 16

    def test_sub_block_prefix_never_indexed(self):
        # the pool hands off whole blocks; a finer match could never be
        # served, so it must not be reported as a hit
        idx = RadixPrefixIndex(block_size=4)
        assert idx.insert([1, 2, 3], "a") == 0
        assert idx.lookup([1, 2, 3]) == (None, 0)
        assert idx.stats()["hit_rate"] == 0.0

    def test_live_filter_falls_back_to_shorter_claim(self):
        idx = RadixPrefixIndex(block_size=4)
        toks = list(range(12))
        idx.insert(toks, "b")        # b claims depths 1..3
        idx.insert(toks[:8], "a")    # a re-claims depths 1..2
        assert idx.lookup(toks, live={"b"}) == ("b", 12)
        # with b inadmissible the best ADMISSIBLE claim is a's, shorter
        assert idx.lookup(toks, live={"a"}) == ("a", 8)
        assert idx.lookup(toks, live={"c"}) == (None, 0)

    def test_evict_replica_drops_its_claims(self):
        idx = RadixPrefixIndex(block_size=4)
        toks = list(range(8))
        idx.insert(toks, "a")
        assert idx.evict_replica("a") == 2
        assert idx.lookup(toks) == (None, 0)

    def test_lru_bound_holds(self):
        idx = RadixPrefixIndex(block_size=4, max_nodes=3)
        for i in range(8):
            idx.insert([i * 10 + d for d in range(4)], "a")
        assert idx.stats()["nodes"] <= 3
        # the most recent insert survived the pruning
        assert idx.lookup([70, 71, 72, 73]) == ("a", 4)

    def test_validation(self):
        with pytest.raises(ValueError, match="block_size"):
            RadixPrefixIndex(block_size=0)
        with pytest.raises(ValueError, match="max_nodes"):
            RadixPrefixIndex(block_size=4, max_nodes=0)


# -- KV handoff ledger ------------------------------------------------------


class TestHandoffLedger:
    def test_matched_roundtrip_books_both_sides(self):
        cap = _CapRouter()
        led = HandoffLedger(router=cap)
        seq = led.book_out(rid=7, src="r0", n_blocks=2, nbytes=4096, tick=3)
        led.book_in(seq, dst="r1", n_blocks=2, nbytes=4096, tick=3)
        audit = led.audit()
        assert audit["matched"] is True
        assert audit["handoffs"] == 1 and audit["abandoned"] == 0
        assert audit["bytes_out"] == audit["bytes_in"] == 4096
        assert audit["open"] == [] and audit["mismatched"] == []
        sides = [r["side"] for r in cap.records if r["kind"] == "handoff"]
        assert sides == ["out", "in"]
        assert all(r["id"] == 7 and r["src"] == "r0" for r in cap.records)

    def test_open_exchange_fails_the_audit(self):
        led = HandoffLedger()
        seq = led.book_out(rid=0, src="r0", n_blocks=1, nbytes=100, tick=0)
        audit = led.audit()
        assert audit["matched"] is False and audit["open"] == [seq]

    def test_byte_mismatch_is_surfaced(self):
        led = HandoffLedger()
        seq = led.book_out(rid=0, src="r0", n_blocks=1, nbytes=100, tick=0)
        led.book_in(seq, dst="r1", n_blocks=1, nbytes=96, tick=0)
        audit = led.audit()
        assert audit["matched"] is False and audit["mismatched"] == [seq]

    def test_abandon_closes_without_matching(self):
        cap = _CapRouter()
        led = HandoffLedger(router=cap)
        seq = led.book_out(rid=1, src="r0", n_blocks=1, nbytes=100, tick=2)
        led.abandon(seq, tick=2, reason="no_adopter")
        audit = led.audit()
        # a deliberate drop is CLOSED, not lost: the audit still matches
        assert audit["matched"] is True and audit["abandoned"] == 1
        assert cap.records[-1]["side"] == "abandoned"
        assert cap.records[-1]["reason"] == "no_adopter"

    def test_double_close_and_unknown_seq_refused(self):
        led = HandoffLedger()
        with pytest.raises(ValueError, match="never booked out"):
            led.book_in(99, dst="r1", n_blocks=1, nbytes=1, tick=0)
        seq = led.book_out(rid=0, src="r0", n_blocks=1, nbytes=1, tick=0)
        led.book_in(seq, dst="r1", n_blocks=1, nbytes=1, tick=0)
        with pytest.raises(ValueError, match="already closed"):
            led.book_in(seq, dst="r2", n_blocks=1, nbytes=1, tick=0)
        with pytest.raises(ValueError, match="already closed"):
            led.abandon(seq, tick=0, reason="late")


# -- autoscaler -------------------------------------------------------------


class TestFleetAutoscaler:
    def _scaler(self, cap=None, **kw):
        kw.setdefault("ttft_budget_s", 1.0)
        kw.setdefault("min_replicas", 1)
        kw.setdefault("max_replicas", 4)
        kw.setdefault("breach_ticks", 2)
        kw.setdefault("clear_ticks", 3)
        return FleetAutoscaler(router=cap, **kw)

    def test_breach_debounce_then_scale_up(self):
        cap = _CapRouter()
        sc = self._scaler(cap)
        assert sc.observe(0, 2.0, 2) is None     # one breach: debounced
        assert sc.observe(1, 2.0, 2) == "scale_up"
        rec = cap.records[-1]
        assert rec["check"] == "autoscale" and rec["action"] == "scale_up"
        assert sc.stats()["scale_ups"] == 1

    def test_none_signal_holds_the_counters(self):
        # a dead spot in the signal is not evidence either way: the
        # breach streak neither grows nor resets
        sc = self._scaler()
        assert sc.observe(0, 2.0, 2) is None
        assert sc.observe(1, None, 2) is None
        assert sc.observe(2, 2.0, 2) == "scale_up"

    def test_hysteresis_band_resets_both_streaks(self):
        sc = self._scaler()
        sc.observe(0, 2.0, 2)                    # breach streak 1
        assert sc.observe(1, 0.5, 2) is None     # in-band: resets
        assert sc.observe(2, 2.0, 2) is None     # streak restarts at 1
        assert sc.observe(3, 2.0, 2) == "scale_up"

    def test_bounds_respected(self):
        sc = self._scaler()
        sc.observe(0, 2.0, 4)
        assert sc.observe(1, 2.0, 4) is None     # already at max
        sc2 = self._scaler()
        for t in range(3):
            sc2.observe(t, 0.01, 1)
        assert sc2.observe(3, 0.01, 1) is None   # already at min

    def test_clear_streak_scales_down(self):
        sc = self._scaler()
        assert sc.observe(0, 0.01, 2) is None
        assert sc.observe(1, 0.01, 2) is None
        assert sc.observe(2, 0.01, 2) == "scale_down"
        assert sc.stats()["scale_downs"] == 1

    def test_validation(self):
        with pytest.raises(ValueError, match="ttft_budget_s"):
            FleetAutoscaler(0.0, 1, 4)
        with pytest.raises(ValueError, match="min_replicas"):
            FleetAutoscaler(1.0, 3, 2)
        with pytest.raises(ValueError, match="breach_ticks"):
            FleetAutoscaler(1.0, 1, 4, breach_ticks=0)
        with pytest.raises(ValueError, match="low_water"):
            FleetAutoscaler(1.0, 1, 4, low_water=1.5)


# -- replica health machine -------------------------------------------------


class _FakeEngine:
    """The slice of the engine surface Replica touches: start() and the
    load signal's queue/lane tables."""

    def __init__(self):
        self.started = False
        self._queue = []
        self._active = {}

    def start(self):
        self.started = True


class TestReplica:
    def _replica(self, cap=None, factory=None, **policy_kw):
        factory = factory or (lambda name, inc: _FakeEngine())
        policy = RemediationPolicy(**policy_kw) if policy_kw else None
        return Replica("r0", factory, policy=policy, router=cap)

    def test_role_validation(self):
        with pytest.raises(ValueError, match="role"):
            Replica("r0", lambda n, i: _FakeEngine(), role="oracle")

    def test_kill_books_nothing_and_stays_dispatchable(self):
        # a silent death has no oracle: the router keeps dispatching to
        # it until the heartbeat watchdog fires — re-dispatch repairs it
        cap = _CapRouter()
        rep = self._replica(cap)
        rep.kill()
        assert not rep.alive and not rep.healthy
        assert rep.dispatchable
        assert cap.records == []

    def test_detect_restart_probation_readmit_walk(self):
        cap = _CapRouter()
        rep = self._replica(cap, probation_steps=2, max_restarts=2)
        rep.kill()
        rep.miss(), rep.miss()
        assert rep.detect(5) == "restart"
        assert rep.case_state == "detected"
        assert rep.restart(5) is True
        assert rep.alive and rep.incarnation == 1 and rep.restarts == 1
        assert rep.case_state == "probation"
        assert rep.dispatchable and not rep.healthy
        rep.probation_tick(6)
        assert rep.case_state == "probation"   # one clean tick of two
        rep.probation_tick(7)
        assert rep.case_state is None and rep.healthy
        actions = [r["action"] for r in cap.records]
        assert actions == ["detected", "restarted", "readmitted"]
        assert cap.records[0]["missed_beats"] == 2
        assert cap.records[-1]["verdict"] == TERMINAL_VERDICTS["recovered"]

    def test_double_detect_refused(self):
        rep = self._replica()
        rep.kill()
        rep.detect(0)
        with pytest.raises(ValueError, match="open case"):
            rep.detect(1)

    def test_quarantine_removes_from_dispatch_set(self):
        rep = self._replica()
        rep.kill()
        rep.detect(0)
        rep.quarantine(0)
        assert rep.case_state == "quarantined"
        assert not rep.dispatchable

    def test_restart_budget_exhaustion_escalates(self):
        cap = _CapRouter()
        rep = self._replica(cap, max_restarts=0)
        rep.kill()
        rep.detect(0)
        assert rep.restart(0) is False
        assert rep.case_state == "escalated"
        assert not rep.alive and not rep.dispatchable
        rec = cap.records[-1]
        assert rec["action"] == "escalated"
        assert rec["verdict"] == TERMINAL_VERDICTS["escalated"]

    def test_failing_relaunch_factory_escalates(self):
        calls = {"n": 0}

        def factory(name, incarnation):
            calls["n"] += 1
            if calls["n"] > 1:      # first build fine, relaunch broken
                raise RuntimeError("broken build")
            return _FakeEngine()

        rep = self._replica(factory=factory)
        rep.kill()
        rep.detect(0)
        # re-running does not fix a broken build: FAILURE, not retry
        assert rep.restart(0) is False
        assert rep.case_state == "escalated" and not rep.alive

    def test_load_signal(self):
        rep = self._replica()
        rep.engine._queue.extend([1, 2])
        rep.engine._active[0] = object()
        assert rep.load == 3
        assert rep.stats()["load"] == 3


# -- fleet config -----------------------------------------------------------


class TestFleetConfig:
    def test_defaults_valid(self):
        cfg = FleetConfig()
        assert cfg.replicas == 2 and cfg.prefill_replicas == 0

    def test_validation(self):
        with pytest.raises(ValueError, match="replicas"):
            FleetConfig(replicas=0)
        with pytest.raises(ValueError, match="decode replica"):
            FleetConfig(replicas=2, prefill_replicas=2)
        with pytest.raises(ValueError, match="min_replicas"):
            FleetConfig(min_replicas=5, max_replicas=4)
        with pytest.raises(ValueError, match="miss_ticks_to_detect"):
            FleetConfig(miss_ticks_to_detect=0)


# -- slow tier: the gate and the ACCEPTANCE chaos drill ---------------------


def test_fleet_selftest_gate():
    """The ``python -m apex_tpu.serving --selftest --fleet`` gate exits
    0 — disaggregated parity through a ledgered KV handoff, then a chaos
    replica kill with failover, restart/readmit and an SLO scale-up."""
    from apex_tpu.serving.__main__ import main

    assert main(["--selftest", "--fleet"]) == 0


def test_fleet_chaos_drill():
    """ISSUE 16 acceptance: a seeded Poisson load pumped into a
    disaggregated 3-replica fleet (the PR-13 generator drives the fleet
    UNCHANGED — drop-in submit/cancel/tick), with a chaos replica kill
    mid-load and the autoscaler armed. Asserts: the kill fired and
    failover re-dispatched the orphans, an SLO scale-up happened, p99
    TTFT of completed requests stays inside the drill budget, every
    global id reaches exactly one terminal record (zero silent drops),
    the handoff ledger closes matched, zero steady-state compiles, and
    the fleet-wide goodput partition identity holds digit-for-digit."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTModel
    from apex_tpu.monitor import MemorySink, MetricRouter
    from apex_tpu.monitor.goodput import account, run_header
    from apex_tpu.resilience.chaos import FaultPlan
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.serving.fleet import FleetRouter
    from apex_tpu.serving.loadgen import PoissonLoadGenerator
    from apex_tpu.transformer import TransformerConfig

    # the p99 bound covers what the drill deliberately pays for: two
    # recovery compile bursts on the CPU mesh (the scale-up engine's
    # warmup and the restarted incarnation's, ~3 s each) plus the
    # standing queue — observed ~6.5 s; the bound catches unbounded
    # stalls, not the booked envelopes
    ttft_drill_budget_s = 15.0
    tcfg = TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4, vocab_size=61,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, position_embedding_type="rope",
        compute_dtype=jnp.float32,
    )
    model = GPTModel(config=tcfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    cfg = ServingConfig(lanes=2, block_size=8, num_blocks=16,
                        max_seq_len=32, max_queue_depth=64, seed=0)
    mem = MemorySink(kinds=("request", "run", "span", "fleet", "handoff",
                            "trace", "slo"))
    router = MetricRouter([mem])
    run_header(router, "fleet-chaos-drill")
    fleet = FleetRouter(
        lambda name, inc: ServingEngine(model, variables, cfg,
                                        router=router),
        FleetConfig(
            replicas=3, prefill_replicas=1, miss_ticks_to_detect=2,
            # the AUTOSCALER's budget, not admission's: a micro-budget so
            # the armed estimate provably breaches under load and the
            # scale-up fires inside the drill window
            ttft_budget_s=1e-4, breach_ticks=2,
            min_replicas=1, max_replicas=4,
        ),
        router=router,
        fault_plan=FaultPlan(kill_replica_steps={12}),
    )
    fleet.start()
    gen = PoissonLoadGenerator(
        rate_rps=150.0, vocab=61, n_requests=40,
        prompt_len=(4, 24), max_new=(4, 8), seed=7,
    )
    # inject the seeded Poisson schedule on a virtual clock (explicit
    # ``now``): the whole load is standing when the tick-12 kill fires,
    # so the victim is provably loaded and failover has work to re-home
    gen.pump(fleet, now=0.0)
    gen.pump(fleet, now=1e6)
    assert gen.done and len(gen.submitted) == 40
    n = 0
    while not fleet.idle and n < 800:
        fleet.tick()
        n += 1
    for _ in range(10):     # probation needs clean ticks past idle
        fleet.tick()
    report = fleet.drain(grace_s=10.0)
    router.close()
    assert n < 800, "fleet never went idle under the drill load"
    assert report["timed_out"] == 0

    records = mem.snapshot()
    fleet_records = [r for r in records if r.get("kind") == "fleet"]
    actions = {(r.get("check"), r.get("action")) for r in fleet_records}

    # 1. the kill fired mid-load and failover re-homed the orphans
    assert ("chaos", "kill_replica") in actions
    assert ("replica", "detected") in actions
    assert ("replica", "restarted") in actions
    assert any(r.get("check") == "failover" and r.get("redispatched", 0) > 0
               for r in fleet_records), "failover re-dispatched nothing"
    assert fleet.redispatched > 0

    # 2. the SLO breach scaled the fleet up
    assert ("autoscale", "scale_up") in actions
    assert ("autoscale", "added") in actions

    # 3. exactly one terminal record per global id — no silent drops,
    # through the kill, the re-dispatches and the handoffs
    req_records = [r for r in records if r.get("kind") == "request"]
    terminal = {}
    for r in req_records:
        if r.get("terminal"):
            terminal.setdefault(r["id"], []).append(r["state"])
    assert set(terminal) == set(range(fleet._next_rid))
    assert all(len(v) == 1 for v in terminal.values())
    assert {v[0] for v in terminal.values()} <= lifecycle.TERMINAL_STATES

    # 4. every request completed (the latest attempt's Request — a
    # re-dispatched request terminates on its second-attempt object)
    reqs = fleet.requests()
    assert len(reqs) == 40
    assert all(r.state == "completed" for r in reqs)
    assert any(r.tags.get("attempt", 1) > 1 for r in reqs), \
        "the kill orphaned nothing — the drill never exercised failover"

    # 5. p99 TTFT held through the kill (honest clock: re-dispatched
    # requests keep their ORIGINAL submit time)
    ttfts = [r.ttft_s for r in reqs if r.ttft_s is not None]
    assert len(ttfts) == 40
    assert percentile(ttfts, 99.0) <= ttft_drill_budget_s

    # 6. every handoff byte is booked both sides and matched
    audit = fleet.ledger.audit()
    assert audit["handoffs"] > 0 and audit["matched"] is True

    # 7. zero steady-state compiles: the restart and scale-up bursts
    # were booked under their own spans, never charged to survivors
    assert fleet.stats()["steady_state_compiles"] == 0

    # 8. recovery time is attributed: failover and handoff are phases
    phases = {r.get("phase") for r in records if r.get("kind") == "span"}
    assert "failover" in phases and "handoff" in phases

    # 9. the goodput partition identity, fleet-wide, with ==
    acct = account(records)
    lhs = acct.productive_s
    for phase in sorted(acct.badput_s):
        lhs = lhs + acct.badput_s[phase]
    assert lhs + acct.unattributed_s == acct.wall_s
    assert acct.productive_s > 0.0

    # 10. ISSUE 17 trace closure: one complete span tree per terminal
    # request — through the kill (attempt > 1) and the handoffs — with
    # the per-request partition identity holding digit-for-digit
    # through a json round trip, and the failover/handoff badput
    # reconciling exactly between the accountant and the gp twins
    from apex_tpu.serving.trace.analyze import analyze as xray

    xr = xray(records)
    assert xr.n_traces > 0 and xr.ok, xr.summary()
    assert not xr.untraced_terminals and not xr.identity_violations
    deco = {d["trace"]: d for d in xr.decompositions}
    assert all(deco[r.rid]["recovery_s"] > 0.0 for r in reqs
               if r.tags.get("attempt", 1) > 1), \
        "failed-over requests must book recovery as its own phase"
    assert all(v["match"] for v in xr.reconcile.values()), xr.summary()

    # 11. the SLO burn monitor saw the micro-budget violations and the
    # fast-burn alert fed the autoscaler (secondary evidence)
    slo_recs = [r for r in records if r.get("kind") == "slo"]
    assert any(r.get("alert") for r in slo_recs)
    assert all(r["n"] >= r["violations"] >= r["sheds"] >= 0
               for r in slo_recs)


# -- KV handoff with a decode step in flight --------------------------------


@pytest.mark.parametrize("ticks", [1, 4])
def test_extract_adopt_with_a_step_in_flight(ticks):
    """The source keeps one decode step in flight across ticks; ``extract``
    reads it first, so the handed-over cache and ``tokens_out`` agree, and
    the adopter (itself mid-step on a bystander) goes on to the tokens of
    an engine that was never interrupted."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTModel
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer import TransformerConfig

    model = GPTModel(config=TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4, vocab_size=61,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, position_embedding_type="rope",
        compute_dtype=jnp.float32,
    ))
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    cfg = ServingConfig(lanes=2, block_size=8, num_blocks=12,
                        max_seq_len=32, prefill_buckets=(8, 16), seed=0)
    prompt, other = np.random.RandomState(3).randint(0, 61, size=(2, 9))
    engines = [ServingEngine(model, variables, cfg).start()
               for _ in range(3)]
    for eng in engines:
        # the compile watcher is process-wide: the others' start-up
        # compiles are booked, not steady-state
        eng.acknowledge_compiles()
    plain, src, dst = engines
    want = plain.submit(prompt.astype(np.int32), max_new_tokens=10)
    while not plain.idle:
        plain.tick()
    assert want.state == "completed"

    bystander = dst.submit(other.astype(np.int32), max_new_tokens=12)
    dst.tick()
    req = src.submit(prompt.astype(np.int32), max_new_tokens=10, rid=5)
    for _ in range(ticks):
        src.tick()
    assert src._inflight is not None and dst._inflight is not None
    seen = len(req.tokens_out)
    payload = src.extract(req.rid)
    assert payload is not None and src.idle
    # the step in flight was read: its token joined, the cursor follows it
    assert len(req.tokens_out) == seen + 1
    assert payload["last_token"] == req.tokens_out[-1]
    assert payload["position"] == len(prompt) + len(req.tokens_out) - 1
    assert dst.adopt(payload)
    while not dst.idle:
        dst.tick()
    assert req.state == "completed" and req.tokens_out == want.tokens_out
    assert bystander.state == "completed"
    assert all(e.steady_state_compiles == 0 for e in engines)
    assert dst.allocator.free_blocks == cfg.num_blocks


def test_extract_of_a_request_its_step_in_flight_completes():
    """A request whose last token is in flight completes when ``extract``
    reads the step: nothing is handed over."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTModel
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer import TransformerConfig

    model = GPTModel(config=TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4, vocab_size=61,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, position_embedding_type="rope",
    ))
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))
    eng = ServingEngine(model, variables, ServingConfig(
        lanes=2, block_size=8, num_blocks=8, max_seq_len=32,
        prefill_buckets=(8,), seed=0)).start()
    req = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
    eng.tick()      # prefill's token, and the last step dispatched
    assert req.state == "decode" and len(req.tokens_out) == 1
    assert eng.extract(req.rid) is None
    assert req.state == "completed" and len(req.tokens_out) == 2
    assert eng.idle and eng.allocator.free_blocks == 8
