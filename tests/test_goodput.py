"""Run-level goodput: span ledger, accountant, fleet health, perf gate.

The unit half of the goodput acceptance (the end-to-end half lives in
tests/test_examples.py, which asserts the GPT example's emitted
``kind="goodput"`` record): the partition identity is hand-counted on a
synthetic multi-incarnation, multi-host fixture, the fleet detector is
exercised on synthetic per-host streams, and the perf-regression gate's
exit codes are pinned — 0 on the recorded BENCH trajectory, nonzero on
a seeded 20% tokens/s regression replay.

Everything here is jax-free by design (the goodput package's contract:
a stream is accountable, and the gate runnable, on any box); the
subprocess tests prove it by poisoning jax in the child.
"""

import json
import os
import signal
import subprocess
import sys

import pytest

from apex_tpu.monitor import MemorySink, MetricRouter
from apex_tpu.monitor import goodput
from apex_tpu.monitor.goodput import accountant, fleet, sentinel, spans
from apex_tpu.monitor.goodput.__main__ import main as goodput_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def router():
    mem = MemorySink()
    r = MetricRouter([mem])
    r.mem = mem
    yield r
    goodput.set_router(None)
    r.close()


# ---------------------------------------------------------------------------
# span ledger


class TestSpans:
    def test_span_record_schema(self, router):
        with goodput.span("compile", step=3, router=router):
            pass
        (rec,) = router.mem.records
        assert {"t", "step", "kind", "host", "phase", "start", "dur_s"} <= set(
            rec
        )
        assert rec["kind"] == "span" and rec["phase"] == "compile"
        assert rec["step"] == 3 and rec["host"] == 0
        assert rec["dur_s"] >= 0.0 and "interrupted" not in rec

    def test_taxonomy_is_closed(self, router):
        with pytest.raises(ValueError, match="closed"):
            with goodput.span("coffee_break", router=router):
                pass
        assert list(router.mem.records) == []

    def test_no_router_is_noop(self):
        goodput.set_router(None)
        with goodput.span("init"):
            pass  # measured and dropped; no crash

    def test_global_router_and_override(self, router):
        goodput.set_router(router)
        other = MemorySink()
        with goodput.span("init"):
            pass
        with goodput.span("step", router=MetricRouter([other])):
            pass
        assert [r["phase"] for r in router.mem.records] == ["init"]
        assert [r["phase"] for r in other.records] == ["step"]

    def test_begin_span_close_idempotent(self, router):
        s = goodput.begin_span("data_wait", router=router)
        assert s.close() is not None
        assert s.close() is None  # second close: no second record
        assert len(router.mem.records) == 1

    def test_flush_open_spans_marks_interrupted(self, router):
        s = goodput.begin_span("step", step=7, router=router)
        n = goodput.flush_open_spans()
        assert n == 1
        (rec,) = router.mem.records
        assert rec["interrupted"] is True and rec["phase"] == "step"
        assert s.close() is None  # flushed spans are closed

    def test_run_header_fields(self, router):
        rec = goodput.run_header(router, "run-abc", steps=12)
        assert rec["kind"] == "run" and rec["run_id"] == "run-abc"
        assert rec["pid"] == os.getpid() and rec["steps"] == 12
        assert isinstance(rec["mono"], float)

    def test_derive_run_id_anchored_vs_random(self, tmp_path):
        a = goodput.derive_run_id(str(tmp_path / "ckpt"))
        b = goodput.derive_run_id(str(tmp_path / "ckpt"))
        c = goodput.derive_run_id(str(tmp_path / "other"))
        assert a == b != c  # restartable join key: same --save, same id
        assert goodput.derive_run_id() != goodput.derive_run_id()


# ---------------------------------------------------------------------------
# accountant


def _span(phase, start, dur, host=0, **extra):
    return {"kind": "span", "step": -1, "host": host, "phase": phase,
            "start": float(start), "dur_s": float(dur), **extra}


def _header(mono, host=0, run_id="job1"):
    return {"kind": "run", "step": 0, "host": host, "run_id": run_id,
            "mono": float(mono)}


def _fixture_records():
    """The hand-counted two-incarnation, two-host fixture.

    host 0 / incarnation A (anchor 0, end 10.5 -> wall 10.5):
      init [0,4], ckpt_restore [1,3] nested in it, compile [4,7],
      steps [7,8][8,9][9,10], ckpt_save [9.5,10.5] overlapping the last
      step. Priority attribution: productive 3.0, ckpt_save exposed 0.5,
      ckpt_restore 2.0, compile 3.0, init [0,1]+[3,4] = 2.0.
    host 0 / incarnation B (restart; fresh monotonic clock at 100):
      one step [100,101] -> wall 1.0, productive 1.0.
    host 1 (one incarnation): step [0,2] -> wall 2.0, productive 2.0.

    Totals: wall 13.5, productive 6.0, badput ckpt_save 0.5,
    ckpt_restore 2.0, compile 3.0, init 2.0, unattributed 0.0;
    3 incarnations, hosts (0, 1), 9 spans. All values exact binary
    floats, so the asserts below use ==, never approx.
    """
    recs = [
        _header(0.0, host=0),
        _header(0.0, host=1),
        _span("init", 0.0, 4.0, host=0),
        _span("step", 0.0, 2.0, host=1),
        _span("ckpt_restore", 1.0, 2.0, host=0),
        _span("compile", 4.0, 3.0, host=0),
        _span("step", 7.0, 1.0, host=0),
        _span("step", 8.0, 1.0, host=0),
        _span("step", 9.0, 1.0, host=0),
        _span("ckpt_save", 9.5, 1.0, host=0),
        # the restart: a second header on host 0 re-anchors the clock
        _header(100.0, host=0),
        _span("step", 100.0, 1.0, host=0),
    ]
    # non-span kinds in the same stream are ignored by the accountant
    recs.append({"kind": "metrics", "step": 1, "host": 0, "loss": 1.0})
    return recs


class TestAccountant:
    def test_hand_counted_partition(self):
        rep = accountant.account(_fixture_records())
        assert rep.wall_s == 13.5
        assert rep.productive_s == 6.0
        assert rep.badput_s == {
            "ckpt_save": 0.5, "ckpt_restore": 2.0, "rollback": 0.0,
            "snapshot": 0.0, "compile": 3.0, "data_wait": 0.0, "stall": 0.0,
            "incident": 0.0, "remediation": 0.0, "drain": 0.0,
            "handoff": 0.0, "failover": 0.0,
            "init": 2.0, "shutdown": 0.0,
        }
        assert rep.unattributed_s == 0.0
        assert rep.incarnations == 3
        assert rep.hosts == (0, 1)
        assert rep.n_spans == 9 and rep.n_interrupted == 0
        assert rep.goodput_fraction == 6.0 / 13.5

    def test_identity_digit_for_digit(self):
        # messy, non-representable durations: the identity must still be
        # EXACT because wall_s is defined as the canonical field sum
        recs = [_header(0.0)]
        t = 0.0
        for i in range(40):
            phase = spans.PHASE_PRIORITY[i % len(spans.PHASE_PRIORITY)]
            dur = 0.1 + 0.013 * i
            recs.append(_span(phase, t, dur))
            t += dur * 0.7  # overlap every successive pair
        rep = accountant.account(recs)
        f = rep.fields()
        total = f["productive_s"]
        for phase in accountant.BADPUT_PHASES:
            total = total + f[f"badput_{phase}_s"]
        total = total + f["unattributed_s"]
        assert total == f["wall_s"]  # ==, never approx
        # and the identity survives a json round trip (the jsonl story)
        g = json.loads(json.dumps(f))
        total = g["productive_s"]
        for phase in accountant.BADPUT_PHASES:
            total = total + g[f"badput_{phase}_s"]
        assert total + g["unattributed_s"] == g["wall_s"]

    def test_overlap_never_double_counts(self):
        # an async ckpt_save fully covered by steps is FREE (off the
        # critical path): zero badput, the TorchTitan design goal
        recs = [
            _header(0.0),
            _span("step", 0.0, 4.0),
            _span("ckpt_save", 1.0, 2.0),
        ]
        rep = accountant.account(recs)
        assert rep.productive_s == 4.0
        assert rep.badput_s["ckpt_save"] == 0.0
        assert rep.wall_s == 4.0

    def test_header_anchors_unattributed(self):
        # wall before the first span (imports, interpreter startup) is
        # unattributed, not silently dropped: the header's mono anchors
        recs = [_header(0.0), _span("step", 5.0, 1.0)]
        rep = accountant.account(recs)
        assert rep.wall_s == 6.0
        assert rep.productive_s == 1.0 and rep.unattributed_s == 5.0

    def test_run_id_filter(self):
        recs = _fixture_records() + [
            _header(0.0, host=0, run_id="other"),
            _span("step", 0.0, 50.0, host=0),
        ]
        rep = accountant.account(recs, run_id="job1")
        assert rep.wall_s == 13.5 and rep.incarnations == 3
        other = accountant.account(recs, run_id="other")
        assert other.wall_s == 50.0 and other.incarnations == 1

    def test_serving_phases_are_productive_and_drain_is_envelope(self):
        # serving taxonomy (PR 13): prefill/decode seconds are the
        # serving analogue of step seconds (PRODUCTIVE_PHASES), and a
        # drain span is an ENVELOPE — the decode ticks inside it stay
        # productive, only the exposed remainder books as drain badput.
        # Hand count: wall [0,10]; prefill [0,2] + decode [2,5]+[6,8]
        # productive = 7.0; drain envelope [5,10] minus the covered
        # [6,8] = 3.0 badput; unattributed [5,6)? no — drain covers it.
        recs = [
            _header(0.0),
            _span("prefill", 0.0, 2.0),
            _span("decode", 2.0, 3.0),
            _span("drain", 5.0, 5.0),
            _span("decode", 6.0, 2.0),
        ]
        rep = accountant.account(recs)
        assert rep.wall_s == 10.0
        assert rep.productive_s == 7.0
        assert rep.badput_s["drain"] == 3.0
        assert rep.unattributed_s == 0.0
        f = rep.fields()
        assert "badput_drain_s" in f and "badput_prefill_s" not in f

    def test_headerless_legacy_stream(self):
        rep = accountant.account([_span("step", 2.0, 3.0)])
        assert rep.incarnations == 1
        assert rep.wall_s == 3.0 and rep.productive_s == 3.0

    def test_interrupted_and_garbage_spans(self):
        recs = [
            _header(0.0),
            _span("step", 0.0, 1.0, interrupted=True),
            _span("step", 1.0, float("nan")),        # skipped
            _span("step", 2.0, -5.0),                # clamped to zero
            {"kind": "span", "host": 0, "phase": "step"},  # no times
            _span("warp_drive", 0.0, 9.0),           # unknown phase
        ]
        rep = accountant.account(recs)
        assert rep.n_interrupted == 1
        assert rep.productive_s == 1.0
        assert rep.wall_s == 2.0  # [0, 2]: the clamped span still anchors

    def test_read_records_skips_torn_lines(self, tmp_path):
        path = tmp_path / "r.jsonl"
        path.write_text(
            json.dumps(_header(0.0)) + "\n"
            + json.dumps(_span("step", 0.0, 1.0)) + "\n"
            + '{"kind": "span", "truncat'  # the killed run's last line
        )
        recs = accountant.read_records([str(path)])
        assert len(recs) == 2
        assert accountant.account(recs).productive_s == 1.0


# ---------------------------------------------------------------------------
# fleet health


def _host_steps(host, durs):
    return [_span("step", i, d, host=host) for i, d in enumerate(durs)]


class TestFleet:
    def test_straggler_flagged_one_sided(self):
        recs = (
            _host_steps(0, [1.0, 1.0, 1.0])
            + _host_steps(1, [1.01, 1.01, 1.01])
            + _host_steps(2, [0.99, 0.99, 0.99])
            + _host_steps(3, [2.0, 2.0, 2.0])     # the straggler
        )
        rep = fleet.detect_divergence(recs)
        assert not rep.ok
        (s,) = rep.stragglers
        assert s["host"] == 3 and s["median_step_s"] == 2.0 and s["z"] > 4
        assert "STRAGGLER host 3" in rep.summary()

    def test_fast_host_not_flagged(self):
        # one-sided: an anomalously FAST host blocks nobody
        recs = (_host_steps(0, [1.0] * 3) + _host_steps(1, [1.01] * 3)
                + _host_steps(2, [0.99] * 3) + _host_steps(3, [0.2] * 3))
        assert fleet.detect_divergence(recs).stragglers == []

    def test_zero_mad_outlier_still_flagged(self):
        # all other hosts identical: MAD is 0, and any slower deviation
        # is infinitely many MADs out — must flag, not divide by zero
        recs = (_host_steps(0, [1.0] * 3) + _host_steps(1, [1.0] * 3)
                + _host_steps(2, [1.0] * 3) + _host_steps(3, [1.2] * 3))
        (s,) = fleet.detect_divergence(recs).stragglers
        assert s["host"] == 3

    def test_two_hosts_cannot_name_a_straggler(self):
        recs = _host_steps(0, [1.0] * 3) + _host_steps(1, [9.0] * 3)
        rep = fleet.detect_divergence(recs)
        assert rep.stragglers == [] and rep.ok

    def test_corruption_suspect(self):
        def metrics(host, step, loss):
            return {"kind": "metrics", "step": step, "host": host,
                    "loss": loss, "grad_norm": 1.0}

        recs = [metrics(h, s, 2.5) for h in range(3) for s in range(4)]
        recs.append(metrics(2, 5, 2.5))
        recs.append(metrics(0, 5, 2.5))
        recs.append(metrics(1, 5, 7.0))  # host 1 diverged at step 5
        rep = fleet.detect_divergence(recs)
        (s,) = rep.suspects
        assert s == {"step": 5, "field": "loss", "host": 1,
                     "value": 7.0, "median": 2.5}
        assert "CORRUPTION SUSPECT host 1" in rep.summary()

    def test_nonfinite_on_one_host_is_suspect(self):
        recs = [
            {"kind": "metrics", "step": 1, "host": 0, "loss": 2.0},
            {"kind": "metrics", "step": 1, "host": 1, "loss": float("nan")},
        ]
        (s,) = fleet.detect_divergence(recs).suspects
        assert s["host"] == 1

    def test_all_hosts_nonfinite_is_not_sdc(self):
        # every host agrees the loss blew up: diverged together (the
        # PR-1 sentinel's job), not silent corruption
        recs = [
            {"kind": "metrics", "step": 1, "host": h, "loss": float("nan")}
            for h in range(3)
        ]
        assert fleet.detect_divergence(recs).suspects == []

    def test_to_records_schema(self):
        recs = (_host_steps(0, [1.0] * 3) + _host_steps(1, [1.01] * 3)
                + _host_steps(2, [0.99] * 3) + _host_steps(3, [2.0] * 3))
        out = fleet.detect_divergence(recs).to_records()
        (rec,) = out
        assert rec["kind"] == "fleet" and rec["check"] == "straggler"
        assert rec["flagged_host"] == 3
        assert {"t", "step", "host"} <= set(rec)


# ---------------------------------------------------------------------------
# perf-regression sentinel


def _meas(metric, value, platform="run", source="test"):
    return {"metric": metric, "value": value, "unit": None,
            "platform": platform, "source": source}


class TestSentinel:
    def test_noise_tolerance_floor_without_repeats(self):
        assert sentinel.noise_tolerance([]) == 0.05
        assert sentinel.noise_tolerance([100.0]) == 0.05

    def test_noise_tolerance_widens_with_repeat_spread(self):
        # best 110; repeats within 15% of it = {100, 110} (90 is 18% off,
        # excluded): med 105, MAD 5, tol = 3 * 5/105 = 1/7 > the 5% floor
        assert sentinel.noise_tolerance([100.0, 110.0, 90.0]) == pytest.approx(
            3.0 * 5.0 / 105.0
        )

    def test_trajectory_progress_is_not_noise(self):
        # rounds 23 -> 2626 -> 2626: the early cpu-era value must not
        # widen the band to "anything goes"
        tol = sentinel.noise_tolerance([23.0, 2626.0, 2626.0])
        assert tol == 0.05  # two identical repeats: MAD 0, floor applies

    def test_regression_and_clean(self):
        history = [_meas("tokens_per_s", 1000.0)]
        (f,) = sentinel.check_regression([_meas("tokens_per_s", 790.0)],
                                         history)
        assert f.rule == "perf.regression" and f.severity == "error"
        assert f.data["baseline"] == 1000.0
        assert sentinel.check_regression([_meas("tokens_per_s", 960.0)],
                                         history) == []

    def test_lower_is_better_direction(self):
        history = [_meas("step_ms", 100.0)]
        (f,) = sentinel.check_regression([_meas("step_ms", 130.0)], history)
        assert f.rule == "perf.regression"
        assert sentinel.check_regression([_meas("step_ms", 95.0)],
                                         history) == []

    def test_no_baseline_is_info_not_error(self):
        (f,) = sentinel.check_regression([_meas("new_metric", 5.0)], [])
        assert f.rule == "perf.no-baseline" and f.severity == "info"

    def test_platform_mismatch_is_no_baseline(self):
        history = [_meas("tokens_per_s", 1000.0, platform="tpu")]
        (f,) = sentinel.check_regression([_meas("tokens_per_s", 10.0,
                                                platform="cpu")], history)
        assert f.rule == "perf.no-baseline"

    def test_measurements_from_records_medians(self):
        recs = [
            {"kind": "metrics", "step": i, "host": 0,
             "tokens_per_s": v, "step_ms": 100.0}
            for i, v in enumerate([900.0, 1000.0, 1100.0])
        ]
        recs.append({"kind": "bench", "step": 0, "host": 0,
                     "metric": "imgs", "value": 42.0, "platform": "tpu"})
        recs.append({"kind": "goodput", "step": 0, "host": 0,
                     "goodput_fraction": 0.9})
        out = {(m["metric"], m["platform"]): m["value"]
               for m in sentinel.measurements_from_records(recs)}
        assert out[("tokens_per_s", "run")] == 1000.0  # median, not mean
        assert out[("step_ms", "run")] == 100.0
        assert out[("imgs", "tpu")] == 42.0
        assert out[("goodput_fraction", "run")] == 0.9

    def test_allowlist_requires_reason_and_suppresses(self):
        from apex_tpu.analysis.findings import AllowlistEntry

        with pytest.raises(ValueError, match="reason"):
            AllowlistEntry(rule="perf.regression", match="tokens", reason="")
        findings = sentinel.check_regression(
            [_meas("tokens_per_s", 500.0)], [_meas("tokens_per_s", 1000.0)])
        allow = sentinel.goodput_allowlist().extended([AllowlistEntry(
            rule="perf.regression", match="tokens_per_s",
            reason="traded tokens/s for the verified-checkpoint path",
        )])
        res = allow.apply(findings, check_stale=False)
        assert res.ok and len(res.suppressed) == 1

    def test_repo_allowlist_is_empty(self):
        # the recorded trajectory stands un-waived; any entry added here
        # is a reviewable claim, and this pin makes adding one deliberate
        assert len(sentinel.goodput_allowlist()) == 0


# ---------------------------------------------------------------------------
# CLI (in-process; the subprocess/jax-free property is pinned below)


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


class TestCLI:
    def test_account_mode_and_json(self, tmp_path, capsys):
        stream = tmp_path / "run.jsonl"
        _write_jsonl(stream, _fixture_records())
        out_json = tmp_path / "out.jsonl"
        rc = goodput_main([str(stream), "--json", str(out_json)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "goodput: 6.000s productive of 13.500s wall" in out
        (rec,) = [json.loads(l) for l in open(out_json)]
        assert rec["kind"] == "goodput" and rec["wall_s"] == 13.5

    def test_account_no_spans_exits_nonzero(self, tmp_path):
        stream = tmp_path / "empty.jsonl"
        _write_jsonl(stream, [{"kind": "metrics", "step": 0, "loss": 1.0}])
        assert goodput_main([str(stream)]) == 1

    def test_fleet_mode_exit_codes(self, tmp_path):
        bad = tmp_path / "bad.jsonl"
        _write_jsonl(bad, _host_steps(0, [1.0] * 3)
                     + _host_steps(1, [1.01] * 3)
                     + _host_steps(2, [0.99] * 3)
                     + _host_steps(3, [2.0] * 3))
        assert goodput_main([str(bad), "--fleet"]) == 1
        ok = tmp_path / "ok.jsonl"
        _write_jsonl(ok, _host_steps(0, [1.0] * 3)
                     + _host_steps(1, [1.0] * 3))
        assert goodput_main([str(ok), "--fleet"]) == 0

    def test_check_without_a_stream_is_a_usage_error(self):
        # there is no recorded trajectory to self-test: --check gates a
        # stream against a --baseline, so no stream is a usage error
        with pytest.raises(SystemExit) as exc:
            goodput_main(["--check"])
        assert exc.value.code == 2

    def test_check_seeded_regression_fails(self, tmp_path, capsys):
        # ACCEPTANCE: a 20% tokens/s regression replay exits nonzero
        def run_records(tokens_per_s):
            return [
                {"kind": "metrics", "step": i, "host": 0,
                 "tokens_per_s": tokens_per_s, "mfu": 0.4, "step_ms": 100.0}
                for i in range(3)
            ]

        baseline = tmp_path / "baseline.jsonl"
        _write_jsonl(baseline, run_records(1000.0))
        fresh = tmp_path / "fresh.jsonl"
        _write_jsonl(fresh, run_records(800.0))
        rc = goodput_main([str(fresh), "--check", "--baseline",
                           str(baseline)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "perf.regression" in out and "tokens_per_s" in out
        # control: the same run replayed against itself passes
        same = tmp_path / "same.jsonl"
        _write_jsonl(same, run_records(1000.0))
        assert goodput_main([str(same), "--check", "--baseline",
                             str(baseline)]) == 0


# ---------------------------------------------------------------------------
# teardown + jax-free subprocess pins


_CHILD_PRELUDE = """
import sys
class _Poison:
    def find_module(self, name, path=None):
        if name in ("jax", "jaxlib", "flax"):
            raise ImportError("poisoned: " + name)
sys.meta_path.insert(0, _Poison())
import json, os
from apex_tpu.monitor import JsonlSink, MetricRouter
from apex_tpu.monitor import goodput
"""


def _run_child(code, timeout=60):
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, "-c", _CHILD_PRELUDE + code],
        capture_output=True, text=True, env=env, timeout=timeout,
    )


class TestTeardown:
    def test_atexit_flushes_open_spans_jax_free(self, tmp_path):
        # a run that forgets to close its spans (or dies past the loop)
        # still lands them, marked interrupted — and the whole producer
        # stack imports with jax POISONED (the any-box contract)
        stream = tmp_path / "run.jsonl"
        code = f"""
router = MetricRouter([JsonlSink({str(stream)!r})])
goodput.run_header(router, "run-x")
goodput.set_router(router)
goodput.begin_span("step", step=5)
"""
        proc = _run_child(code)
        assert proc.returncode == 0, proc.stderr
        recs = [json.loads(l) for l in open(stream)]
        assert recs[0]["kind"] == "run"
        (span_rec,) = [r for r in recs if r["kind"] == "span"]
        assert span_rec["interrupted"] is True and span_rec["step"] == 5

    @pytest.mark.skipif(os.name != "posix", reason="posix signals")
    def test_sigterm_flushes_then_dies_by_sigterm(self, tmp_path):
        # the chaos harness's real-SIGTERM drill: the in-flight span
        # must land (interrupted) AND the process must still die by
        # SIGTERM — the flush hook converts nothing into a survival
        stream = tmp_path / "run.jsonl"
        code = f"""
import signal, time
router = MetricRouter([JsonlSink({str(stream)!r})])
goodput.run_header(router, "run-sig")
goodput.set_router(router)
goodput.begin_span("ckpt_save", step=9)
os.kill(os.getpid(), signal.SIGTERM)
time.sleep(30)  # never reached: the handler re-raises SIGTERM
"""
        proc = _run_child(code)
        assert proc.returncode == -signal.SIGTERM, (proc.returncode,
                                                    proc.stderr)
        recs = [json.loads(l) for l in open(stream)]
        (span_rec,) = [r for r in recs if r["kind"] == "span"]
        assert span_rec["phase"] == "ckpt_save"
        assert span_rec["interrupted"] is True
