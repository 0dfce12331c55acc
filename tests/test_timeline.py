"""Timeline analyzer: the math pinned digit-for-digit on synthetic traces.

Every number the analyzer reports — compute/collective/memcpy union
seconds, exposed-comms time, overlap and bubble fractions, achieved
bytes/s per axis — is asserted here against hand-counted fixtures
(including async ``-start``/``-done`` pairs and overlapping device
lanes), the same pinning discipline as tests/test_xray.py's byte
formulas and test_analysis.py's HLO inventory. The trace-event PARSER
is fed synthetic dicts (the ``parse_trace(data)`` seam, mirroring
``parse_hlo_module(text)``); whether the RUNNING jax still writes that
schema is the analysis gate's trace-schema smoke
(apex_tpu/analysis/trace_smoke.py), exercised directly at the bottom.

The end-to-end round trip over the real dp2xtp2 GPT example
(``--profile-analyze``) lives in tests/test_examples.py
(test_gpt_pretrain_profile_analyze, slow tier).
"""

import gzip
import json
import os

import pytest

from apex_tpu.monitor.xray.timeline import (
    StepSpan,
    TimelineReport,
    analyze,
    classify_op,
    pair_async_collectives,
    parse_logdir,
    parse_trace,
)
from apex_tpu.monitor.xray.timeline.analyzer import (
    StepBreakdown,
    intersect_intervals,
    merge_intervals,
    op_base,
    subtract_intervals,
    total_us,
)
from apex_tpu.monitor.xray.timeline.parser import TraceEvent


def ev(name, ts, dur, pid=2, tid=0, **args):
    """A device-op event dict (args.hlo_op = its own stem, the CPU
    exporter's shape)."""
    return {"ph": "X", "name": name, "pid": pid, "tid": tid, "ts": ts,
            "dur": dur, "args": {"hlo_op": name, **args}}


def step_marker(step, ts, dur, pid=1, tid=0):
    """A StepTraceAnnotation span (step_num stringified, as on the wire)."""
    return {"ph": "X", "name": "train", "pid": pid, "tid": tid, "ts": ts,
            "dur": dur, "args": {"step_num": str(step)}}


def trace_dict(*events):
    return {"traceEvents": list(events), "displayTimeUnit": "ns"}


# ---------------------------------------------------------------------------
# parser


class TestParser:
    def test_not_a_trace_raises(self):
        with pytest.raises(ValueError, match="traceEvents"):
            parse_trace({"foo": 1})

    def test_metadata_lanes_and_events(self):
        tl = parse_trace(trace_dict(
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/host:CPU"}},
            {"ph": "M", "name": "thread_name", "pid": 7, "tid": 3,
             "args": {"name": "python"}},
            ev("fusion.1", 10.0, 5.0, pid=7, tid=3),
        ))
        assert tl.process_names == {7: "/host:CPU"}
        assert tl.thread_names == {(7, 3): "python"}
        (e,) = tl.events
        assert tl.lane(e) == "/host:CPU/python"
        assert e.end == 15.0

    def test_step_spans_stringified_and_sorted(self):
        tl = parse_trace(trace_dict(
            step_marker(1, 100.0, 50.0),
            step_marker(0, 0.0, 100.0),
            # unparseable step_num is not a marker
            {"ph": "X", "name": "train", "pid": 1, "tid": 0, "ts": 0,
             "dur": 1, "args": {"step_num": "warmup"}},
        ))
        spans = tl.step_spans()
        assert [(s.step, s.ts, s.end) for s in spans] == [
            (0, 0.0, 100.0), (1, 100.0, 150.0),
        ]
        assert spans[0].dur == 100.0

    def test_device_ops_prefer_hlo_op_and_exclude_markers(self):
        tl = parse_trace(trace_dict(
            step_marker(0, 0.0, 100.0),
            ev("dot.1", 10.0, 5.0),
            # host noise without hlo_op is not a device op
            {"ph": "X", "name": "ThreadpoolListener::run", "pid": 1,
             "tid": 0, "ts": 0.0, "dur": 90.0, "args": {}},
        ))
        assert [e.name for e in tl.device_op_events()] == ["dot.1"]

    def test_device_process_fallback_tpu_layout(self):
        # no args.hlo_op anywhere (TPU exporter): /device: processes are
        # the op lanes, "XLA Ops" threads preferred when labeled
        tl = parse_trace(trace_dict(
            {"ph": "M", "name": "process_name", "pid": 9,
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "name": "thread_name", "pid": 9, "tid": 1,
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "name": "thread_name", "pid": 9, "tid": 2,
             "args": {"name": "Steps"}},
            {"ph": "X", "name": "fusion.3", "pid": 9, "tid": 1,
             "ts": 5.0, "dur": 2.0, "args": {}},
            {"ph": "X", "name": "bookkeeping", "pid": 9, "tid": 2,
             "ts": 5.0, "dur": 2.0, "args": {}},
            {"ph": "X", "name": "host_thing", "pid": 1, "tid": 0,
             "ts": 5.0, "dur": 2.0, "args": {}},
        ))
        assert [e.name for e in tl.device_op_events()] == ["fusion.3"]

    def test_parse_logdir_newest_capture_merged(self, tmp_path):
        def write(run, host, *events):
            d = tmp_path / "plugins" / "profile" / run
            d.mkdir(parents=True, exist_ok=True)
            with gzip.open(d / f"{host}.trace.json.gz", "wt") as f:
                json.dump(trace_dict(*events), f)

        write("2026_01_01_00_00_00", "old", ev("stale.1", 0.0, 1.0))
        write("2026_01_02_00_00_00", "host_a", ev("dot.1", 0.0, 1.0))
        write("2026_01_02_00_00_00", "host_b", ev("dot.2", 0.0, 1.0, pid=3))
        tl, files = parse_logdir(str(tmp_path))
        assert len(files) == 2
        assert all("2026_01_02" in f for f in files)
        assert sorted(e.name for e in tl.events) == ["dot.1", "dot.2"]

    def test_parse_logdir_empty_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="trace.json"):
            parse_logdir(str(tmp_path))

    def test_plain_json_also_readable(self, tmp_path):
        d = tmp_path / "plugins" / "profile" / "run"
        d.mkdir(parents=True)
        (d / "h.trace.json").write_text(
            json.dumps(trace_dict(ev("dot.1", 0.0, 1.0)))
        )
        tl, _ = parse_logdir(str(tmp_path))
        assert [e.name for e in tl.events] == ["dot.1"]


# ---------------------------------------------------------------------------
# op classification


class TestClassify:
    @pytest.mark.parametrize("name,cls", [
        ("fusion.42", "compute"),
        ("dot.1", "compute"),
        ("%convolution.7", "compute"),
        ("reduce.7", "compute"),           # a plain reduce is NOT comms
        ("transpose.5", "compute"),        # burns core time, not wire
        ("all-reduce.17", "collective"),
        ("all-reduce-start.3", "collective"),
        ("all-reduce-done.4", "collective"),
        ("all-gather.2", "collective"),
        ("reduce-scatter.9", "collective"),
        ("collective-permute-start.1", "collective"),
        ("all-to-all.5", "collective"),
        ("copy.3", "memcpy"),
        ("copy-start.8", "memcpy"),
        ("MemcpyD2H", "memcpy"),
        ("infeed.1", "memcpy"),
    ])
    def test_classes(self, name, cls):
        assert classify_op(name) == cls

    def test_op_base_strips_one_ordinal(self):
        assert op_base("all-reduce.17") == "all-reduce"
        assert op_base("%Fusion.2") == "fusion"
        assert op_base("all-reduce") == "all-reduce"
        assert op_base("name.v2.3") == "name.v2"


# ---------------------------------------------------------------------------
# interval algebra


class TestIntervals:
    def test_merge(self):
        assert merge_intervals([(5.0, 7.0), (0.0, 2.0), (1.0, 3.0),
                                (3.0, 4.0), (9.0, 9.0)]) == [
            (0.0, 4.0), (5.0, 7.0),
        ]

    def test_intersect(self):
        a = [(0.0, 10.0), (20.0, 30.0)]
        b = [(5.0, 25.0)]
        assert intersect_intervals(a, b) == [(5.0, 10.0), (20.0, 25.0)]

    def test_subtract(self):
        a = [(0.0, 10.0)]
        b = [(2.0, 3.0), (5.0, 7.0)]
        assert subtract_intervals(a, b) == [
            (0.0, 2.0), (3.0, 5.0), (7.0, 10.0),
        ]
        assert total_us(subtract_intervals(a, b)) == 7.0

    def test_subtract_disjoint_noop(self):
        assert subtract_intervals([(0.0, 5.0)], [(6.0, 8.0)]) == [(0.0, 5.0)]


# ---------------------------------------------------------------------------
# async start/done fusion


class TestAsyncPairing:
    def test_fifo_pairing_ignores_ordinals(self):
        # XLA's -done ordinal does NOT match its -start's; FIFO per
        # (pid, kind) in time order is the pairing rule
        events = [
            TraceEvent("all-gather-start.7", 2, 0, 0.0, 1.0),
            TraceEvent("all-gather-start.8", 2, 0, 2.0, 1.0),
            TraceEvent("all-gather-done.21", 2, 0, 10.0, 1.0),
            TraceEvent("all-gather-done.22", 2, 0, 12.0, 1.0),
        ]
        out = sorted(pair_async_collectives(events), key=lambda o: o.ts)
        assert [(o.name, o.ts, o.end) for o in out] == [
            ("all-gather-start.7", 0.0, 11.0),
            ("all-gather-start.8", 2.0, 13.0),
        ]
        assert all(o.cls == "collective" for o in out)

    def test_unpaired_start_keeps_own_span(self):
        (o,) = pair_async_collectives(
            [TraceEvent("all-reduce-start.1", 2, 0, 5.0, 3.0)]
        )
        assert (o.ts, o.end) == (5.0, 8.0)

    def test_cross_pid_never_pairs(self):
        out = pair_async_collectives([
            TraceEvent("all-reduce-start.1", 2, 0, 0.0, 1.0),
            TraceEvent("all-reduce-done.2", 3, 0, 5.0, 1.0),
        ])
        assert sorted((o.ts, o.end) for o in out) == [(0.0, 1.0), (5.0, 6.0)]

    def test_sync_ops_pass_through(self):
        (o,) = pair_async_collectives(
            [TraceEvent("%all-reduce.4", 2, 0, 1.0, 2.0)]
        )
        assert o.name == "all-reduce.4" and o.cls == "collective"


# ---------------------------------------------------------------------------
# per-step breakdown: the partition, hand-counted


class TestBreakdown:
    def fixture_a(self):
        """One step [0,100]: compute [10,40]+[50,70], collective [30,60],
        memcpy [80,85]."""
        return parse_trace(trace_dict(
            step_marker(0, 0.0, 100.0),
            ev("fusion.1", 10.0, 30.0),
            ev("fusion.2", 50.0, 20.0),
            ev("all-reduce.3", 30.0, 30.0),
            ev("copy.4", 80.0, 5.0),
        ))

    def test_partition_hand_counted(self):
        (s,) = analyze(self.fixture_a()).steps
        assert s.span_us == 100.0
        assert s.compute_us == 50.0          # [10,40] u [50,70]
        assert s.collective_us == 30.0       # [30,60]
        assert s.exposed_collective_us == 10.0   # [40,50]
        assert s.memcpy_us == 5.0
        assert s.exposed_memcpy_us == 5.0    # [80,85] hides under nothing
        assert s.busy_us == 65.0             # [10,70] u [80,85]
        assert s.idle_us == 35.0
        assert s.bubble_fraction == pytest.approx(0.35)
        assert s.overlap_fraction == pytest.approx(1.0 - 10.0 / 30.0)
        assert s.n_ops == 4

    def test_predicted_bubble_join(self):
        """The schedule-algebra join: the caller's predicted bubble
        fraction rides every per-step kind="profile" record next to the
        measured one, and the summary prints the comparison — the
        predicted-vs-measured leg of the zero-bubble proof loop."""
        from apex_tpu.parallel.pipeline import schedule_cost

        cost = schedule_cost("zero_bubble", 4, 8)
        report = analyze(
            self.fixture_a(),
            predicted_bubble_fraction=cost.bubble_fraction,
            schedule="zero_bubble",
        )
        (s,) = report.steps
        recs = [r for r in report.to_records() if "bubble_fraction" in r]
        (r,) = recs
        assert r["predicted_bubble_fraction"] == cost.bubble_fraction
        assert r["schedule"] == "zero_bubble"
        assert r["bubble_fraction"] == pytest.approx(0.35)
        summary = report.summary()
        assert "bubble join (zero_bubble)" in summary
        assert "predicted" in summary and "measured" in summary
        # without the join, neither field appears (the analyzer never
        # invents a prediction)
        plain = analyze(self.fixture_a())
        assert all(
            "predicted_bubble_fraction" not in r for r in plain.to_records()
        )
        assert "bubble join" not in plain.summary()

    def test_cli_schedule_choices_in_sync(self):
        """The CLI's literal --schedule choices (spelled out so the
        no-jax CLI contract holds) must track the algebra registry."""
        from apex_tpu.monitor.xray.timeline.__main__ import (
            _SCHEDULE_CHOICES,
        )
        from apex_tpu.parallel.pipeline.algebra import SCHEDULES

        assert sorted(_SCHEDULE_CHOICES) == sorted(SCHEDULES)

    def test_partition_identity(self):
        (s,) = analyze(self.fixture_a()).steps
        assert (
            s.compute_us + s.exposed_collective_us + s.exposed_memcpy_us
            + s.idle_us
        ) == pytest.approx(s.span_us)

    def test_async_pair_and_overlapping_lanes(self):
        """Step 0: fused async collective [10,50] fully hidden under a
        two-lane compute union [0,60] -> overlap 1.0. Step 1: an
        unpaired -start, no compute -> overlap 0.0, bubble 0.9."""
        tl = parse_trace(trace_dict(
            step_marker(0, 0.0, 100.0),
            step_marker(1, 100.0, 100.0),
            ev("all-gather-start.7", 10.0, 5.0, pid=2),
            ev("all-gather-done.9", 40.0, 10.0, pid=2),
            ev("fusion.1", 0.0, 30.0, pid=3),
            ev("dot.2", 20.0, 40.0, pid=3),
            ev("all-reduce-start.11", 110.0, 10.0, pid=2),
        ))
        s0, s1 = analyze(tl).steps
        assert s0.collective_us == 40.0      # fused [10,50]
        assert s0.compute_us == 60.0         # [0,30] u [20,60] = [0,60]
        assert s0.exposed_collective_us == 0.0
        assert s0.overlap_fraction == pytest.approx(1.0)
        assert s0.busy_us == 60.0 and s0.idle_us == 40.0
        assert s1.collective_us == 10.0
        assert s1.exposed_collective_us == 10.0
        assert s1.overlap_fraction == pytest.approx(0.0)
        assert s1.bubble_fraction == pytest.approx(0.9)

    def test_op_straddling_boundary_clipped_to_each_step(self):
        tl = parse_trace(trace_dict(
            step_marker(0, 0.0, 100.0),
            step_marker(1, 100.0, 100.0),
            ev("fusion.1", 90.0, 20.0),      # [90,110] straddles
        ))
        s0, s1 = analyze(tl).steps
        assert s0.compute_us == 10.0 and s1.compute_us == 10.0
        assert s0.n_ops == 1 and s1.n_ops == 1

    def test_no_markers_synthetic_whole_span(self):
        tl = parse_trace(trace_dict(
            ev("fusion.1", 10.0, 5.0), ev("dot.2", 30.0, 10.0),
        ))
        report = analyze(tl)
        assert report.synthetic_step
        (s,) = report.steps
        assert (s.step, s.ts, s.end) == (-1, 10.0, 40.0)
        assert s.compute_us == 15.0 and s.idle_us == 15.0

    def test_no_ops_no_steps(self):
        report = analyze(parse_trace(trace_dict()))
        assert report.steps == [] and report.n_device_ops == 0
        assert "no steps" in report.summary()

    def test_overlap_none_without_collectives(self):
        s = StepBreakdown(step=0, ts=0, end=10, compute_us=5,
                          collective_us=0, memcpy_us=0,
                          exposed_collective_us=0, exposed_memcpy_us=0,
                          busy_us=5, n_ops=1)
        assert s.overlap_fraction is None


# ---------------------------------------------------------------------------
# the bandwidth join: measured seconds -> predicted bytes, hand-counted


JOIN_HLO = """\
HloModule join_mod, num_partitions=4

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main.5 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p0), channel_id=1, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add.1
  ROOT %all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %all-reduce.1), channel_id=2, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add.1
}
"""


def dp2tp2_mesh():
    import numpy as np
    import jax

    return jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp")
    )


class TestBandwidthJoin:
    def make_ledger(self):
        from apex_tpu.monitor.xray.ledger import CollectiveEntry, CommsLedger

        led = CommsLedger()
        led.entries.append(CollectiveEntry(
            op="psum", axis="dp", axis_size=2, shape=(400,),
            dtype="float32", bytes=1600, ici_bytes=1600,
        ))
        led.entries.append(CollectiveEntry(
            op="psum", axis="tp", axis_size=2, shape=(200,),
            dtype="float32", bytes=800, ici_bytes=400,
        ))
        return led

    def joined_report(self, ici_bandwidth=None):
        from apex_tpu.analysis.hlo import parse_hlo_module

        tl = parse_trace(trace_dict(
            step_marker(0, 0.0, 1000.0),
            # groups {{0,2},{1,3}} vary the dp coordinate -> axis "dp"
            ev("all-reduce.1", 100.0, 200.0),
            # groups {{0,1},{2,3}} vary the tp coordinate -> axis "tp"
            ev("all-reduce.2", 400.0, 100.0),
            # matches no HLO instruction -> counted unattributed
            ev("all-gather.9", 600.0, 50.0),
        ))
        return analyze(
            tl, module=parse_hlo_module(JOIN_HLO), mesh=dp2tp2_mesh(),
            ledger=self.make_ledger(), ici_bandwidth=ici_bandwidth,
        )

    def test_join_hand_counted(self):
        report = self.joined_report(ici_bandwidth=1e8)
        assert report.n_unattributed_collectives == 1
        dp, tp = report.axes
        assert (dp.axis, tp.axis) == ("dp", "tp")
        assert dp.n_events == 1 and tp.n_events == 1
        assert dp.measured_us_per_step == 200.0
        assert tp.measured_us_per_step == 100.0
        assert dp.predicted_bytes_per_step == 1600
        assert dp.predicted_ici_bytes_per_step == 1600
        assert tp.predicted_ici_bytes_per_step == 400
        # 1600 B in 200us = 8e6 B/s; vs 1e8 roofline = 8%
        assert dp.achieved_bytes_per_s == pytest.approx(8e6)
        assert dp.utilization == pytest.approx(0.08)
        # 400 B in 100us = 4e6 B/s
        assert tp.achieved_bytes_per_s == pytest.approx(4e6)

    def test_unknown_roofline_is_none_not_fake(self):
        dp = self.joined_report().axes[0]
        assert dp.roofline_bytes_per_s is None
        assert dp.utilization is None
        assert "roofline unknown" in self.joined_report().summary()

    def test_predicted_axis_without_events_still_reported(self):
        # a predicted axis whose events all vanished from the capture
        # must surface with zero measured time, not silently drop
        from apex_tpu.analysis.hlo import parse_hlo_module

        tl = parse_trace(trace_dict(
            step_marker(0, 0.0, 1000.0),
            ev("all-reduce.1", 100.0, 200.0),   # dp only
        ))
        report = analyze(tl, module=parse_hlo_module(JOIN_HLO),
                         mesh=dp2tp2_mesh(), ledger=self.make_ledger())
        tp = next(a for a in report.axes if a.axis == "tp")
        assert tp.n_events == 0
        assert tp.measured_us_per_step == 0.0
        assert tp.achieved_bytes_per_s is None

    def test_records_share_router_schema(self):
        recs = self.joined_report(ici_bandwidth=1e8).to_records()
        assert all(r["kind"] == "profile" for r in recs)
        assert all({"t", "step", "kind"} <= set(r) for r in recs)
        step_recs = [r for r in recs if "span_ms" in r]
        (s,) = step_recs
        assert s["span_ms"] == pytest.approx(1.0)
        assert (
            s["compute_ms"] + s["exposed_comms_ms"] + s["exposed_memcpy_ms"]
            + s["idle_ms"]
        ) == pytest.approx(s["span_ms"])
        axis_recs = [r for r in recs if "axis" in r]
        assert [r["axis"] for r in axis_recs] == ["dp", "tp"]
        assert axis_recs[0]["utilization"] == pytest.approx(0.08)


# ---------------------------------------------------------------------------
# CLI


class TestCli:
    def write_capture(self, tmp_path, *events):
        d = tmp_path / "plugins" / "profile" / "run"
        d.mkdir(parents=True)
        with gzip.open(d / "h.trace.json.gz", "wt") as f:
            json.dump(trace_dict(*events), f)

    def test_cli_analyzes_and_emits_jsonl(self, tmp_path, capsys):
        from apex_tpu.monitor.xray.timeline.__main__ import main

        self.write_capture(
            tmp_path, step_marker(0, 0.0, 100.0), ev("fusion.1", 10.0, 30.0),
        )
        out_jsonl = tmp_path / "profile.jsonl"
        assert main([str(tmp_path), "--json", str(out_jsonl)]) == 0
        out = capsys.readouterr().out
        assert "timeline: 1 step(s)" in out
        (rec,) = [json.loads(l) for l in out_jsonl.read_text().splitlines()]
        assert rec["kind"] == "profile" and rec["compute_ms"] == 0.03

    def test_cli_empty_dir_fails(self, tmp_path, capsys):
        from apex_tpu.monitor.xray.timeline.__main__ import main

        assert main([str(tmp_path)]) == 1
        assert "timeline:" in capsys.readouterr().err

    def test_cli_works_without_jax(self, tmp_path):
        """The docs' offline claim, pinned: a capture is analyzable on a
        box with NO jax at all (a trace comes back from the chip and is
        reduced wherever there is a Python). The subprocess poisons jax/jaxlib/flax
        in sys.modules so any import along the CLI path fails loudly;
        the lazy PEP-562 package inits are what make this hold."""
        import subprocess
        import sys

        self.write_capture(
            tmp_path, step_marker(0, 0.0, 100.0), ev("fusion.1", 10.0, 30.0),
        )
        code = (
            "import sys\n"
            "for m in ('jax', 'jaxlib', 'flax', 'optax'):\n"
            "    sys.modules[m] = None\n"
            "from apex_tpu.monitor.xray.timeline.__main__ import main\n"
            f"sys.exit(main([{str(tmp_path)!r}]))\n"
        )
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env={**os.environ, "PYTHONPATH": repo}, timeout=60,
        )
        assert proc.returncode == 0, proc.stderr
        assert "timeline: 1 step(s)" in proc.stdout


# ---------------------------------------------------------------------------
# the gate's trace-schema smoke, run directly: THIS jax's exporter must
# still produce captures the analyzer can segment


def test_trace_schema_smoke_clean():
    from apex_tpu.analysis.trace_smoke import timeline_smoke_findings

    fins = timeline_smoke_findings()
    assert fins == [], "\n".join(f.format() for f in fins)
