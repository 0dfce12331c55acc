"""perf/serve_trace.py on a small hand-made trace (nanoseconds):

  chip 0, "XLA Modules":  jit_prefill(11) [0,100)  jit_decode(22) [100,400)
      jit_decode(22) [500,800)  jit_prefill(33) [800,850)
  chip 0, "XLA Ops":  in the first prefill fusion.1 [0,90); in the first
      decode while.2 [100,390) holding copy.3 [100,250), gather_fusion.4
      [250,300) and fusion.5 [300,390); in the second decode copy.3
      [500,700) and fusion.5 [700,790); in the last prefill
      dynamic-slice_bitcast_fusion.6 [800,840); and broadcast.9 [900,910)
      outside every program

so jit_decode ran twice for 600 ns of module time; its ops' self time is
0 (the while) + 150 + 50 + 90 + 200 + 90 = 580, of which copy + gather are
400; jit_prefill ran twice (two buckets, one name) for 150 ns with 130 of
ops, 40 of them a slice.
"""

import pytest

from _bench import load

tr = load("trace_reduce.py")
st = load("serve_trace.py")

FAMILIES = ["copy", "gather", "dynamic-slice", "slice", "transpose"]


def _ev(line, name, start, end, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(end - start)}


@pytest.fixture(scope="module")
def events():
    m, o = "XLA Modules", "XLA Ops"
    return [
        _ev(m, "jit_prefill(11)", 0, 100), _ev(m, "jit_decode(22)", 100, 400),
        _ev(m, "jit_decode(22)", 500, 800), _ev(m, "jit_prefill(33)", 800, 850),
        _ev(o, "fusion.1", 0, 90), _ev(o, "while.2", 100, 390),
        _ev(o, "copy.3", 100, 250), _ev(o, "gather_fusion.4", 250, 300),
        _ev(o, "fusion.5", 300, 390), _ev(o, "copy.3", 500, 700),
        _ev(o, "fusion.5", 700, 790),
        _ev(o, "dynamic-slice_bitcast_fusion.6", 800, 840),
        _ev(o, "broadcast.9", 900, 910),
        _ev("python", "tick", 0, 1000, plane="/host:CPU"),
        # a second chip the one-chip cell does not use
        _ev(m, "jit_decode(22)", 0, 50, plane="/device:TPU:1"),
        _ev(o, "copy.3", 0, 50, plane="/device:TPU:1"),
    ]


def test_program_names_drop_the_run_id():
    assert st.program_name("jit_decode(123456789)") == "jit_decode"
    assert st.program_name("jit_prefill") == "jit_prefill"


def test_runs_module_time_and_op_time_by_program(events):
    p = st.by_program(events, chips=1)
    assert set(p) == {"jit_prefill", "jit_decode", st.OUTSIDE}
    d = p["jit_decode"]
    assert d["runs"] == 2
    assert d["module_s"] == pytest.approx(600e-9)
    assert d["op_s"] == pytest.approx(580e-9)
    assert d["ops"] == pytest.approx({"copy": 350e-9, "gather_fusion": 50e-9,
                                      "fusion": 180e-9})
    assert "while" not in d["ops"]  # its body did all the work
    f = p["jit_prefill"]
    assert f["runs"] == 2 and f["module_s"] == pytest.approx(150e-9)
    assert f["op_s"] == pytest.approx(130e-9)
    assert p[st.OUTSIDE]["ops"] == pytest.approx({"broadcast": 10e-9})
    # a program's ops add up to the busy time trace_reduce books
    r = tr.reduce(events, chips=1, spans=("tick",))
    assert sum(x["op_s"] for x in p.values()) == pytest.approx(r["busy_s"])


def test_a_second_chip_counts_only_when_asked_for(events):
    assert st.by_program(events, chips=2)["jit_decode"]["runs"] == 3
    assert st.by_program(events, chips=1)["jit_decode"]["runs"] == 2


def test_no_device_plane_gives_nothing(events):
    host = [e for e in events if e["plane"].startswith("/host")]
    assert st.by_program(host, chips=1) == {}


def test_family_seconds_reads_fusion_names_by_their_parts():
    ops = {"copy": 3.0, "copy-start": 0.5, "gather_fusion": 1.0,
           "dynamic-slice_bitcast_fusion": 0.25, "fusion": 8.0,
           "transpose_copy_fusion": 0.125, "convolution_convert_fusion": 5.0,
           "copysign_fusion": 9.0, "slice-and-dice": 0.0625}
    assert st.family_seconds(ops, FAMILIES) == pytest.approx(
        3.0 + 0.5 + 1.0 + 0.25 + 0.125 + 0.0625)
    assert st.family_seconds(ops, []) == 0.0


def test_the_three_trace_readers_on_the_hand_made_trace(events):
    import types

    cell = {"programs": {"decode": "jit_decode", "prefill": "jit_prefill"},
            "copy_families": FAMILIES}
    ctx = types.SimpleNamespace(
        cell=cell, counters={"programs": st.by_program(events, chips=1)})
    tick = load("layer_metrics/decode_tick_ms.serve.py")
    copy = load("layer_metrics/decode_copy_share.serve.py")
    pre = load("layer_metrics/prefill_share_pct.serve.py")
    assert tick.read(ctx) == pytest.approx(300e-6)  # ms: 600 ns / 2 runs
    assert copy.read(ctx) == pytest.approx(400.0 / 580.0)
    assert pre.read(ctx) == pytest.approx(100.0 * 130 / (130 + 580 + 10))
