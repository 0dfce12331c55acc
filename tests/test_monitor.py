"""Telemetry subsystem (apex_tpu.monitor): in-step MetricBag, router
fan-out, FLOPs/MFU arithmetic, stall watchdog, profiler trigger, and the
registered-taps lint that keeps ``sow`` names from drifting.

The load-bearing contract is the fetch cadence: metrics cross
device->host ONCE per log interval (every crossing stalls the dispatch
pipeline), so the bag tests count actual
fetches via ``monitor.host_fetch_count`` instead of trusting comments.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P



class TestMetricBag:
    SPEC = {"loss": "mean", "skips": "sum", "scale": "last", "peak": "max"}

    def _filled(self):
        bag = monitor.metric_bag(self.SPEC)
        for v in (1.0, 2.0, 6.0):
            bag = bag.add(
                loss=v, skips=float(v > 1), scale=2 * v, peak=v
            )
        return bag

    def test_mode_math(self):
        vals = monitor.read_bag(self._filled())
        assert vals == {"loss": 3.0, "skips": 2.0, "scale": 12.0, "peak": 6.0}

    def test_unknown_metric_raises(self):
        bag = monitor.metric_bag(self.SPEC)
        with pytest.raises(KeyError, match="lss"):
            bag.add(lss=1.0)

    def test_non_scalar_raises(self):
        bag = monitor.metric_bag(self.SPEC)
        with pytest.raises(ValueError, match="scalar"):
            bag.add(loss=jnp.ones((2,)))

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="modes"):
            monitor.metric_bag({"x": "median"})

    def test_empty_bag_reads_none(self):
        # mean of zero add() calls is 0/0 and max of none is -inf: both
        # must surface as None (json null), never as a fake number
        vals = monitor.read_bag(monitor.metric_bag(self.SPEC))
        assert vals["loss"] is None and vals["peak"] is None

    def test_omitted_metric_semantics(self):
        bag = monitor.metric_bag(self.SPEC).add(scale=4.0)
        vals = monitor.read_bag(bag)
        assert vals["scale"] == 4.0
        # per-metric fold counts: metrics this add() omitted read None
        # (no folds), not a diluted or fake number
        assert vals["loss"] is None
        assert vals["peak"] is None

    def test_non_finite_values_excluded(self):
        """A NaN-poisoned step must not null the whole interval: the
        non-finite fold is dropped and the mean covers the finite steps
        (the anomaly itself is the sentinel's/skip-counter's story)."""
        bag = monitor.metric_bag(self.SPEC)
        bag = bag.add(loss=1.0, scale=2.0, peak=1.0, skips=0.0)
        bag = bag.add(loss=jnp.float32(jnp.nan), scale=jnp.float32(jnp.inf),
                      peak=jnp.float32(jnp.nan), skips=1.0)
        bag = bag.add(loss=3.0, scale=4.0, peak=2.0, skips=0.0)
        vals = monitor.read_bag(bag)
        assert vals["loss"] == 2.0      # mean of the two finite folds
        assert vals["scale"] == 4.0     # inf did not overwrite the gauge
        assert vals["peak"] == 2.0
        assert vals["skips"] == 1.0
        # all-non-finite still reads None, not 0
        nan_only = monitor.metric_bag(self.SPEC).add(
            loss=jnp.float32(jnp.nan)
        )
        assert monitor.read_bag(nan_only)["loss"] is None

    def test_reset_and_reuse(self):
        bag = monitor.reset_bag(self._filled())
        assert int(bag.count) == 0
        vals = monitor.read_bag(bag.add(loss=5.0))
        assert vals["loss"] == 5.0  # no leakage from before the reset

    def test_merge(self):
        a = monitor.metric_bag(self.SPEC).add(loss=1.0, peak=1.0)
        b = monitor.metric_bag(self.SPEC).add(loss=3.0, peak=9.0, scale=7.0)
        vals = monitor.read_bag(a.merge(b))
        # skips got zero folds in either bag -> None, same as unmerged
        assert vals == {"loss": 2.0, "skips": None, "scale": 7.0, "peak": 9.0}

    def test_merge_spec_mismatch_raises(self):
        a = monitor.metric_bag({"x": "mean"})
        b = monitor.metric_bag({"y": "mean"})
        with pytest.raises(ValueError, match="specs"):
            a.merge(b)

    def test_one_fetch_per_interval_under_jit(self):
        """The acceptance contract: a donated bag threads through a jitted
        step for N steps with exactly N/interval host fetches."""

        @jax.jit
        def step(bag, x):
            return bag.add(loss=x, skips=0.0, scale=1.0, peak=x)

        bag = monitor.metric_bag(self.SPEC)
        interval, steps, reads = 4, 12, []
        before = monitor.host_fetch_count()
        for i in range(steps):
            bag = step(bag, jnp.float32(i))
            if (i + 1) % interval == 0:
                reads.append(monitor.read_bag(bag))
                bag = monitor.reset_bag(bag)
        assert monitor.host_fetch_count() - before == steps // interval
        assert [r["loss"] for r in reads] == [1.5, 5.5, 9.5]

    def test_fresh_bag_survives_donation(self):
        """Regression: metric_bag/reset_bag must create DISTINCT buffers
        per metric — a shared zero leaf donated under jit trips XLA's
        'donate the same buffer twice' check (and wedged collectives in
        the GPT example before the fix)."""
        import functools

        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))
        replicated = jax.sharding.NamedSharding(mesh, P())

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(bag, x):
            return bag.add(loss=x, skips=0.0, scale=1.0, peak=x)

        bag = jax.device_put(monitor.metric_bag(self.SPEC), replicated)
        bag = step(bag, jnp.float32(1.0))  # raised before the fix
        bag = jax.device_put(monitor.reset_bag(bag), replicated)
        bag = step(bag, jnp.float32(3.0))
        assert monitor.read_bag(bag)["loss"] == 3.0

    def test_bag_inside_shard_map(self):
        """The example wiring: the bag crosses a compat.shard_map boundary
        with replicated specs while the data is dp-sharded."""
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))

        @jax.jit
        @lambda f: shard_map(
            f, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P(),
            check_vma=False,
        )
        def step(bag, xs):
            loss = jax.lax.pmean(jnp.mean(xs), "dp")
            return bag.add(loss=loss, skips=0.0, scale=1.0, peak=loss)

        bag = monitor.metric_bag(self.SPEC)
        xs = jnp.arange(16, dtype=jnp.float32)
        bag = step(bag, xs)
        assert monitor.read_bag(bag)["loss"] == pytest.approx(7.5)


class TestGradNormTaps:
    def test_global_grad_norm_matches_numpy(self):
        grads = {"a": jnp.asarray([3.0, 4.0]), "b": {"c": jnp.full((2, 2), 1.0)}}
        flat = np.concatenate([np.array([3.0, 4.0]), np.ones(4)])
        assert float(monitor.global_grad_norm(grads)) == pytest.approx(
            np.linalg.norm(flat)
        )

    def test_empty_tree_is_zero(self):
        assert float(monitor.global_grad_norm({})) == 0.0

    def test_per_layer_norms_key_per_top_level_entry(self):
        grads = {
            "params": {
                "layer_0": {"w": jnp.asarray([3.0, 4.0])},
                "layer_1": {"w": jnp.asarray([6.0, 8.0])},
            }
        }
        norms = monitor.per_layer_grad_norms(grads)
        assert set(norms) == {"grad_norm/layer_0", "grad_norm/layer_1"}
        assert float(norms["grad_norm/layer_0"]) == pytest.approx(5.0)
        assert float(norms["grad_norm/layer_1"]) == pytest.approx(10.0)


class TestRouter:
    def test_fan_out_one_schema(self, tmp_path, capsys):
        jsonl = str(tmp_path / "m.jsonl")
        csvp = str(tmp_path / "m.csv")
        mem = monitor.MemorySink()
        router = monitor.MetricRouter(
            [monitor.JsonlSink(jsonl), monitor.CsvSink(csvp),
             monitor.StdoutSink(), mem]
        )
        router.metrics(4, loss=1.2345678, grad_norm=0.5)
        router.event("skip", 5, loss=99.0, lr_scale=1.0)
        router.close()

        lines = [json.loads(l) for l in open(jsonl)]
        assert [l["kind"] for l in lines] == ["metrics", "skip"]
        assert all({"t", "step", "kind"} <= set(l) for l in lines)
        assert lines == list(mem.records)  # deque-backed (bounded) sink
        csv_rows = open(csvp).read().splitlines()
        assert csv_rows[0].startswith("t,step,kind")
        out = capsys.readouterr().out
        assert "step     4 loss   1.2346" in out
        assert "[skip] step 5" in out

    def test_sink_failure_is_isolated(self, caplog):
        class Bomb(monitor.Sink):
            def emit(self, record):
                raise OSError("disk full")

        mem = monitor.MemorySink()
        router = monitor.MetricRouter([Bomb(), mem])
        router.metrics(1, loss=1.0)  # must not raise
        assert len(mem.records) == 1  # later sinks still served

    def test_csv_header_is_frozen(self, tmp_path):
        csvp = str(tmp_path / "m.csv")
        router = monitor.MetricRouter([monitor.CsvSink(csvp)])
        router.metrics(0, loss=1.0)
        router.metrics(1, loss=2.0, surprise=3.0)  # new column: dropped row
        router.metrics(2, loss=4.0)
        router.close()
        rows = open(csvp).read().splitlines()
        assert len(rows) == 3  # header + steps 0 and 2
        assert "surprise" not in rows[0]

    def test_csv_filters_to_metrics_kind(self, tmp_path):
        csvp = str(tmp_path / "m.csv")
        router = monitor.MetricRouter([monitor.CsvSink(csvp)])
        router.event("timer", 0, name="step-time", seconds=0.1)
        router.metrics(0, loss=1.0)
        router.event("skip", 1, loss=9.0)  # anomaly kinds jsonl-only
        router.metrics(2, loss=2.0)
        router.close()
        rows = open(csvp).read().splitlines()
        # header froze on the first METRICS record, not the timer event
        assert rows[0] == "t,step,kind,host,loss" and len(rows) == 3

    def test_csv_resume_keeps_single_header(self, tmp_path):
        csvp = str(tmp_path / "m.csv")
        first = monitor.CsvSink(csvp)
        first.emit(monitor.make_record("metrics", 0, loss=1.0))
        first.close()
        second = monitor.CsvSink(csvp)  # process restart, same path
        second.emit(monitor.make_record("metrics", 1, loss=2.0))
        second.close()
        rows = open(csvp).read().splitlines()
        assert len(rows) == 3  # ONE header + two data rows
        assert sum(r.startswith("t,step,kind") for r in rows) == 1

    def test_timers_plug_into_router(self):
        from apex_tpu.utils import Timers

        mem = monitor.MemorySink()
        router = monitor.MetricRouter([mem])
        timers = Timers(write_fn=router.timer_write_fn)
        timers("fwd").start()
        timers("fwd").stop()
        timers.write(["fwd"], iteration=3)
        (rec,) = mem.records
        assert rec["kind"] == "timer" and rec["step"] == 3
        assert rec["name"] == "fwd-time" and rec["seconds"] >= 0.0

    def test_tensorboard_sink_gated_not_raising(self, tmp_path):
        # whichever way the import probe goes on this box, the gate must
        # return (sink or None) rather than raise
        sink = monitor.try_tensorboard_sink(str(tmp_path / "tb"))
        if sink is not None:
            sink.emit(monitor.make_record("metrics", 1, loss=2.0))
            sink.close()


class TestTimersWriteParity:
    """The reference-parity fix: ``Timers.write`` resets by default, so
    successive writes report per-interval times, not a growing total."""

    def _timer_with(self, timers, name, seconds):
        t = timers(name)
        t.start()
        t.elapsed_ += seconds  # deterministic elapsed; stop() adds ~0
        t.stop()

    def test_write_resets_by_default(self):
        from apex_tpu.utils import Timers

        seen = []
        timers = Timers(write_fn=lambda n, v, it: seen.append(v))
        self._timer_with(timers, "x", 1.0)
        timers.write(["x"], iteration=0)
        self._timer_with(timers, "x", 1.0)
        timers.write(["x"], iteration=1)
        assert seen[0] == pytest.approx(1.0, abs=0.05)
        # the old hard-coded reset=False accumulated: ~2.0 here
        assert seen[1] == pytest.approx(1.0, abs=0.05)

    def test_write_reset_false_accumulates(self):
        from apex_tpu.utils import Timers

        seen = []
        timers = Timers(write_fn=lambda n, v, it: seen.append(v))
        self._timer_with(timers, "x", 1.0)
        timers.write(["x"], iteration=0, reset=False)
        self._timer_with(timers, "x", 1.0)
        timers.write(["x"], iteration=1, reset=False)
        assert seen[1] == pytest.approx(2.0, abs=0.1)

    def test_write_normalizer(self):
        from apex_tpu.utils import Timers

        seen = []
        timers = Timers(write_fn=lambda n, v, it: seen.append(v))
        self._timer_with(timers, "x", 1.0)
        timers.write(["x"], iteration=0, normalizer=4.0)
        assert seen[0] == pytest.approx(0.25, abs=0.05)


def _tiny_cfg(**kw):
    from apex_tpu.transformer import TransformerConfig

    base = dict(
        num_layers=1, hidden_size=4, num_attention_heads=2, vocab_size=8,
        max_position_embeddings=6, ffn_hidden_size=8,
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    base.update(kw)
    return TransformerConfig(**base)


class TestFlops:
    """MFU math against FULLY hand-counted tiny configs (2*m*n*k per
    matmul, per token): any change to the counters must re-derive these
    numbers, not nudge them until green."""

    def test_layer_flops_hand_counted(self):
        cfg = _tiny_cfg()
        # h=4, heads=2, head_dim=2, q=kv=4, ffn=8, s=6:
        #   qkv   2*4*(4+2*4) = 96
        #   attn  2*6*4 + 2*6*4 = 96   (scores + context)
        #   out   2*4*4 = 32
        #   mlp   2*(2*4*8) = 128
        assert monitor.transformer_layer_flops_per_token(cfg, 6) == 352.0

    def test_gqa_shrinks_kv_projection(self):
        cfg = _tiny_cfg(num_query_groups=1)
        # kv = 1 group * head_dim 2 = 2: qkv = 2*4*(4+2*2) = 64 (was 96)
        assert monitor.transformer_layer_flops_per_token(cfg, 6) == 320.0

    def test_gated_mlp_costs_third_matmul(self):
        cfg = _tiny_cfg(activation="swiglu", add_bias_linear=False)
        # mlp 2 mats -> 3 mats: 128 -> 192
        assert monitor.transformer_layer_flops_per_token(cfg, 6) == 416.0

    def test_gpt_adds_logit_head(self):
        cfg = _tiny_cfg()
        # layers + 2*h*vocab = 352 + 2*4*8 = 416
        assert monitor.gpt_flops_per_token(cfg, 6) == 416.0
        # seq_len defaults to max_position_embeddings
        assert monitor.gpt_flops_per_token(cfg) == 416.0

    def test_bert_adds_lm_head(self):
        cfg = _tiny_cfg()
        # layers + dense h*h + vocab proj = 352 + 32 + 64 = 448
        assert monitor.bert_flops_per_token(cfg, 6) == 448.0

    def test_training_is_3x_forward(self):
        assert monitor.training_flops_per_step(416.0, 10) == 3 * 4160.0

    def test_tokens_per_second(self):
        assert monitor.tokens_per_second(100, 2.0) == 50.0
        with pytest.raises(ValueError):
            monitor.tokens_per_second(100, 0.0)

    def test_mfu_math_and_unknown_peak(self, monkeypatch):
        monkeypatch.delenv("APEX_TPU_PEAK_FLOPS", raising=False)
        assert monitor.mfu(1e12, 1.0, 1, peak_flops=2e12) == pytest.approx(0.5)
        assert monitor.mfu(1e12, 0.5, 4, peak_flops=1e12) == pytest.approx(0.5)
        # CPU devices have no peak entry: None, never a made-up number
        assert monitor.mfu(1e12, 1.0, 1) is None

    def test_peak_env_override(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PEAK_FLOPS", "123e9")
        assert monitor.peak_flops_per_device() == pytest.approx(123e9)
        assert monitor.mfu(123e9, 1.0, 1) == pytest.approx(1.0)


class TestStallWatchdog:
    def test_fires_once_and_rearms_on_beat(self):
        fired = []
        dog = monitor.StallWatchdog(
            0.1, on_stall=fired.append, poll_s=0.02
        ).start()
        try:
            dog.beat(7)
            time.sleep(0.35)
            assert len(fired) == 1  # one stall, not one per poll
            assert fired[0]["step"] == 7
            assert fired[0]["overdue_s"] > 0.1
            dog.beat(8)  # recovery re-arms
            time.sleep(0.35)
            assert len(fired) == 2 and fired[1]["step"] == 8
        finally:
            dog.stop()

    def test_no_fire_while_beating(self):
        dog = monitor.StallWatchdog(0.3, poll_s=0.02)
        with dog:
            for i in range(8):
                dog.beat(i)
                time.sleep(0.05)
        assert dog.stalls == []

    def test_handler_exception_does_not_kill_dog(self):
        def boom(info):
            raise RuntimeError("handler bug")

        dog = monitor.StallWatchdog(0.05, on_stall=boom, poll_s=0.02).start()
        try:
            time.sleep(0.15)
            dog.beat(1)
            time.sleep(0.15)
            assert len(dog.stalls) == 2  # survived the first handler crash
        finally:
            dog.stop()

    def test_bad_deadline_rejected(self):
        with pytest.raises(ValueError):
            monitor.StallWatchdog(0.0)

    def test_restart_after_stop(self):
        """Regression: stop() left the _stop event set, so a restarted
        watchdog's thread exited immediately and stalls went unflagged."""
        dog = monitor.StallWatchdog(0.08, poll_s=0.02)
        dog.start()
        dog.stop()
        dog.start()  # e.g. pause around a known-slow restore, then resume
        try:
            time.sleep(0.3)
            assert dog.stalls  # the restarted dog is actually alive
        finally:
            dog.stop()


class TestProfilerTrigger:
    def _drive(self, trigger, steps, verdicts=None):
        @jax.jit
        def work(x):
            return (x @ x).sum()

        for i in range(steps):
            trigger.maybe_start(i)
            out = work(jnp.ones((8, 8)))
            jax.block_until_ready(out)
            if verdicts and i in verdicts:
                trigger.on_verdict(i, verdicts[i])
            trigger.maybe_stop(i)
        trigger.close()

    def test_requested_step_writes_capture_dir(self, tmp_path):
        trigger = monitor.ProfilerTrigger(str(tmp_path), window_steps=2)
        trigger.request(step=2, reason="requested")
        self._drive(trigger, 6)
        (cap,) = trigger.captures
        assert cap["start_step"] == 2 and cap["end_step"] == 3
        assert os.path.isdir(cap["path"])
        # a real capture lands files under the dir (plugins/profile/...)
        assert any(files for _, _, files in os.walk(cap["path"]))

    def test_verdict_escalation_triggers_capture(self, tmp_path):
        from apex_tpu.resilience.sentinel import VERDICT_ROLLBACK, VERDICT_SKIP

        trigger = monitor.ProfilerTrigger(str(tmp_path), window_steps=1)
        self._drive(trigger, 6, verdicts={1: VERDICT_SKIP, 3: VERDICT_ROLLBACK})
        (cap,) = trigger.captures  # SKIP must not trigger; ROLLBACK must
        assert cap["start_step"] == 4 and "verdict" in cap["reason"]

    def test_one_capture_at_a_time(self, tmp_path):
        trigger = monitor.ProfilerTrigger(str(tmp_path), window_steps=4)
        trigger.request(step=0)
        trigger.request(step=1)  # ignored: a request is already pending
        self._drive(trigger, 6)
        assert len(trigger.captures) == 1

    def test_anomaly_outranks_scheduled_request(self, tmp_path):
        """Regression: a far-future --profile-step request must not block
        the on-anomaly capture — the blowup happening NOW wins."""
        from apex_tpu.resilience.sentinel import VERDICT_ROLLBACK

        trigger = monitor.ProfilerTrigger(str(tmp_path), window_steps=1)
        trigger.request(step=1000, reason="requested")
        self._drive(trigger, 5, verdicts={2: VERDICT_ROLLBACK})
        (cap,) = trigger.captures
        assert cap["start_step"] == 3 and "verdict" in cap["reason"]


class TestResilienceRouting:
    def test_anomaly_stream_shares_schema_and_old_path(self, tmp_path):
        from apex_tpu import resilience

        log = str(tmp_path / "anomalies.jsonl")
        mem = monitor.MemorySink()
        mgr = resilience.ResilienceManager(
            log_path=log, router=monitor.MetricRouter([mem])
        )
        mgr.resolve(3, resilience.VERDICT_SKIP, loss=9.9)
        mgr.resolve(4, resilience.VERDICT_HALT, loss=11.0)

        # the legacy jsonl path still works, byte-for-byte schema
        lines = [json.loads(l) for l in open(log)]
        assert lines == list(mem.records) == mgr.events
        assert [l["kind"] for l in lines] == ["skip", "halt"]
        assert all({"t", "step", "kind"} <= set(l) for l in lines)


class TestAmpOptimizerMetrics:
    def test_collect_metrics_exposes_grad_norm(self):
        import optax

        from apex_tpu import amp

        params = {"w": jnp.ones((4,), jnp.float32)}
        params, amp_opt, _ = amp.initialize(
            params, optax.sgd(0.1), opt_level="O2"
        )
        state = amp_opt.init(params)
        scale = float(state.scaler.scale)
        grads = {"w": jnp.full((4,), 3.0 * scale, jnp.float16)}
        _, _, info = amp_opt.step(
            grads, state, params, collect_metrics=True
        )
        # norm of the UNSCALED fp32 grads: ||(3,3,3,3)|| = 6
        assert float(info["grad_norm"]) == pytest.approx(6.0, rel=1e-3)

    def test_metrics_off_by_default(self):
        import optax

        from apex_tpu import amp

        params = {"w": jnp.ones((4,), jnp.float32)}
        params, amp_opt, _ = amp.initialize(
            params, optax.sgd(0.1), opt_level="O2"
        )
        state = amp_opt.init(params)
        _, _, info = amp_opt.step(
            {"w": jnp.ones((4,), jnp.float16)}, state, params
        )
        assert "grad_norm" not in info


class TestLayerMetricsTap:
    def test_layer_out_rms_sown_and_readable(self, rng):
        from apex_tpu.transformer.layer import ParallelTransformer

        cfg = _tiny_cfg(num_layers=2, collect_layer_metrics=True)
        model = ParallelTransformer(config=cfg)
        x = jnp.ones((6, 2, 4), cfg.compute_dtype)  # (s, b, h)
        params = model.init(rng, x)
        y, col = model.apply(params, x, mutable=["intermediates"])
        taps = monitor.taps_from_intermediates(col["intermediates"])
        assert "layer_out_rms" in taps
        assert np.isfinite(float(taps["layer_out_rms"]))
        assert float(taps["layer_out_rms"]) > 0.0

    def test_tap_off_by_default(self, rng):
        from apex_tpu.transformer.layer import ParallelTransformer

        cfg = _tiny_cfg(num_layers=1)
        model = ParallelTransformer(config=cfg)
        x = jnp.ones((6, 2, 4), cfg.compute_dtype)
        params = model.init(rng, x)
        _, col = model.apply(params, x, mutable=["intermediates"])
        assert monitor.taps_from_intermediates(col.get("intermediates", {})) == {}


class TestRegisteredTapsLint:
    """Tier-1 drift guard: every ``sow("intermediates", <name>, ...)`` in
    apex_tpu/ must be registered in monitor/taps.py, and every registry
    row must still have a live sow site. THIN WRAPPER: the rule logic
    migrated to the unified AST lint framework
    (apex_tpu.analysis.lint, rule ``lint.registered-taps``); these test
    names are kept so the tier-1 history stays legible."""

    def _findings(self):
        from apex_tpu.analysis import lint

        return lint.run_lint(rules=["lint.registered-taps"])

    def test_every_sown_tap_is_registered(self):
        unregistered = [
            f for f in self._findings() if not f.data.get("stale")
        ]
        assert not unregistered, (
            "sow taps missing from monitor/taps.py REGISTERED_TAPS: "
            + "; ".join(f.format() for f in unregistered)
        )

    def test_every_registered_tap_is_still_sown(self):
        stale = [f for f in self._findings() if f.data.get("stale")]
        assert not stale, (
            "REGISTERED_TAPS entries with no sow site left: "
            + "; ".join(f.format() for f in stale)
        )


class TestRawCollectiveLint:
    """Tier-1 drift guard (the REGISTERED_TAPS pattern, for comms): no
    call site in apex_tpu/ may invoke ``lax.{psum,all_gather,...}``
    directly — every collective goes through the xray ledger wrappers so
    the comms ledger sees ALL of apex_tpu's traffic. THIN WRAPPER over
    apex_tpu.analysis.lint rule ``lint.raw-collective``; the allowlist
    (ledger.py itself) now lives in apex_tpu/analysis/allowlist.py with
    its reason, and staleness is the framework's require_hit check."""

    def _result(self):
        from apex_tpu.analysis import Allowlist, lint
        from apex_tpu.analysis.allowlist import REPO_ALLOWLIST

        fins = lint.run_lint(rules=["lint.raw-collective"])
        rule_entries = [
            e for e in REPO_ALLOWLIST.entries
            if e.rule == "lint.raw-collective"
        ]
        return Allowlist(rule_entries).apply(fins, check_stale=True)

    def test_no_raw_collective_bypasses_the_ledger(self):
        res = self._result()
        assert not res.findings, (
            "raw jax.lax collective call sites bypass the xray comms "
            "ledger (use apex_tpu.monitor.xray.ledger wrappers, or add "
            "an allowlist entry with a reason): "
            + "; ".join(f.format() for f in res.findings)
        )

    def test_allowlist_is_not_stale(self):
        """Every allowlist entry for this rule must still suppress a live
        raw-collective site — otherwise remove it."""
        res = self._result()
        assert not res.stale_entries, (
            "stale lint.raw-collective allowlist entries: "
            + ", ".join(e.match for e in res.stale_entries)
        )


class TestRecordSchemaHost:
    """The ``host`` field (PR 7): every record carries the producing
    process's fleet index so merged multi-host streams stay
    attributable, resolved without importing (or initializing) jax."""

    def test_make_record_defaults_host_zero(self):
        rec = monitor.make_record("metrics", 3, loss=1.0)
        assert set(rec) == {"t", "step", "kind", "host", "loss"}
        assert rec["host"] == 0  # single-process runs are host 0

    def test_env_override_and_explicit_kwarg(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_HOST", "5")
        assert monitor.make_record("span", 0)["host"] == 5
        # an explicit host= (replaying another host's stream) wins
        assert monitor.make_record("span", 0, host=2)["host"] == 2
        monkeypatch.setenv("APEX_TPU_HOST", "not-an-int")
        assert monitor.make_record("span", 0)["host"] == 0

    def test_csv_resume_tolerates_pre_host_header(self, tmp_path):
        """A CSV written before the schema grew ``host`` must resume
        cleanly: the adopted old header lacks the column and the sink
        drops the field instead of rejecting every record."""
        csvp = tmp_path / "m.csv"
        csvp.write_text("t,step,kind,loss\n1.0,0,metrics,1.5\n")
        sink = monitor.CsvSink(str(csvp))
        sink.emit(monitor.make_record("metrics", 1, loss=2.5))
        # a genuinely NEW data column is still rejected (header frozen)
        with pytest.raises(ValueError):
            sink.emit(monitor.make_record("metrics", 2, loss=1.0,
                                          surprise=9.0))
        sink.close()
        rows = open(csvp).read().splitlines()
        assert len(rows) == 3 and "host" not in rows[0]
        assert rows[2].endswith(",2.5")

    def test_stdout_sink_hides_plumbing(self, capsys):
        sink = monitor.StdoutSink()
        sink.emit(monitor.make_record("metrics", 1, loss=1.0))
        # span/run records fire per loop iteration for the accountant,
        # not the console; host is schema plumbing on every kind
        sink.emit(monitor.make_record("span", 1, phase="step", start=0.0,
                                      dur_s=0.1))
        sink.emit(monitor.make_record("run", 0, run_id="r"))
        out = capsys.readouterr().out
        assert "step     1" in out and "host" not in out
        assert "span" not in out and "run_id" not in out

    def test_tensorboard_sink_skips_host_scalar(self, tmp_path):
        tb = monitor.try_tensorboard_sink(str(tmp_path))
        if tb is None:
            pytest.skip("no TensorBoard writer importable")
        calls = []
        tb._writer.add_scalar = lambda *a: calls.append(a)
        tb.emit(monitor.make_record("metrics", 1, loss=1.0))
        assert [c[0] for c in calls] == ["metrics/loss"]


class TestRouterLifecycle:
    """PR 7 satellite: MetricRouter is a context manager with idempotent
    close and a best-effort exit flush, so an abnormal termination can't
    tear buffered records off the stream."""

    def test_context_manager_closes_sinks(self, tmp_path):
        closed = []

        class Tracker(monitor.MemorySink):
            def close(self):
                closed.append(True)

        with monitor.MetricRouter([Tracker()]) as router:
            router.metrics(0, loss=1.0)
        assert closed == [True]

    def test_close_is_idempotent(self):
        closed = []

        class Tracker(monitor.MemorySink):
            def close(self):
                closed.append(True)

        router = monitor.MetricRouter([Tracker()])
        router.close()
        router.close()  # the exit teardown re-closing is a no-op
        assert closed == [True]

    def test_emit_after_close_drops_with_one_warning(self, monkeypatch):
        from apex_tpu.monitor import router as router_mod

        warnings = []
        monkeypatch.setattr(
            router_mod.logger, "warning",
            lambda msg, *args: warnings.append(msg % args),
        )
        mem = monitor.MemorySink()
        router = monitor.MetricRouter([mem])
        router.close()
        router.metrics(1, loss=1.0)  # daemon thread racing shutdown
        router.metrics(2, loss=2.0)
        assert len(mem.records) == 0
        assert sum("after router close" in w for w in warnings) == 1

    def test_flush_hooks_run_before_routers_close(self):
        from apex_tpu.monitor import router as router_mod

        order = []
        mem = monitor.MemorySink()
        router = monitor.MetricRouter([mem])
        router_mod.register_flush_hook(
            lambda: order.append("hook") or router.event("span", 0,
                                                         phase="stall"))
        try:
            router_mod._flush_all_routers()
            # the hook's record landed BEFORE the router closed
            assert order == ["hook"]
            assert [r["kind"] for r in mem.records] == ["span"]
            assert router._closed
        finally:
            router_mod._FLUSH_HOOKS.clear()


class TestStallRouting:
    """PR 7 satellite: stalls land in the record stream (kind='stall' +
    a phase='stall' span the goodput accountant books as badput), not
    only in logger.warning and the in-memory list."""

    def test_stall_emits_event_and_span(self):
        mem = monitor.MemorySink()
        router = monitor.MetricRouter([mem])
        dog = monitor.StallWatchdog(0.08, poll_s=0.02, router=router).start()
        try:
            dog.beat(4)
            time.sleep(0.3)
        finally:
            dog.stop()
        by_kind = {}
        for rec in mem.records:
            by_kind.setdefault(rec["kind"], []).append(rec)
        (stall,) = by_kind["stall"]
        assert stall["step"] == 4 and stall["overdue_s"] > 0.08
        (span_rec,) = by_kind["span"]
        assert span_rec["phase"] == "stall" and span_rec["step"] == 4
        # the span covers the dead time measured from the LAST heartbeat
        assert span_rec["dur_s"] == pytest.approx(stall["overdue_s"])

    def test_profiler_trigger_router_records_capture(self, tmp_path):
        mem = monitor.MemorySink()
        router = monitor.MetricRouter([mem])
        trigger = monitor.ProfilerTrigger(str(tmp_path), window_steps=2,
                                          router=router)
        trigger.request(step=1, reason="requested")

        @jax.jit
        def work(x):
            return (x @ x).sum()

        for i in range(4):
            trigger.maybe_start(i)
            jax.block_until_ready(work(jnp.ones((8, 8))))
            trigger.maybe_stop(i)
        trigger.close()
        (rec,) = [r for r in mem.records if r["kind"] == "profile"]
        assert rec["step"] == 1 and rec["end_step"] == 2
        assert rec["reason"] == "requested" and os.path.isdir(rec["path"])


class TestMemorySinkKinds:
    def test_kinds_filter_keeps_window_for_the_consumer(self):
        # the examples' goodput window: metrics/timer traffic must not
        # evict the run header and spans the accountant needs
        mem = monitor.MemorySink(max_records=4, kinds=("run", "span"))
        mem.emit(monitor.make_record("run", 0, run_id="r"))
        for i in range(100):
            mem.emit(monitor.make_record("metrics", i, loss=1.0))
        mem.emit(monitor.make_record("span", 1, phase="step"))
        assert [r["kind"] for r in mem.records] == ["run", "span"]

    def test_default_keeps_everything(self):
        mem = monitor.MemorySink()
        mem.emit(monitor.make_record("metrics", 0, loss=1.0))
        mem.emit(monitor.make_record("span", 0, phase="step"))
        assert len(mem.records) == 2
