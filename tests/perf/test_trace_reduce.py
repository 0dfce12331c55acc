"""perf/trace_reduce.py on a small recorded trace, against hand-worked
numbers. The recording (fixtures/trace_events.json, nanoseconds):

  chip 0, line "XLA Ops":  while.7 [0,100) holding fusion.1 [0,40) and
      self_attention.3 [40,100); fusion.1 [150,250); self_attention.3 [400,500)
  chip 1, line "XLA Ops":  fusion.9 [0,250)
  host spans:  fetch_batch [90,160)  step [160,380)  fetch_loss [380,520)

so on chip 0 the device is busy over [0,100) + [150,250) + [400,500) = 300 ns
of a window [0,520), and idle over [100,150), [250,400) and [500,520).
"""

import json
import os

import pytest

from _bench import FIXTURES, load

tr = load("trace_reduce.py")
SPANS = ("fetch_batch", "step", "fetch_loss")


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(FIXTURES, "trace_events.json")) as f:
        return json.load(f)


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)]) == [
        [0, 4], [5, 12]]


def test_busy_union_and_window(events):
    r = tr.reduce(events, chips=1, spans=SPANS)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(520e-9)
    # the while and its body overlap: the union counts [0,100) once
    assert r["busy_s"] == pytest.approx(300e-9)
    idle_share = 1.0 - r["busy_s"] / r["window_s"]
    assert idle_share == pytest.approx(220.0 / 520.0)


def test_busy_is_averaged_over_the_chips_used(events):
    r = tr.reduce(events, chips=2, spans=SPANS)
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((300e-9 + 250e-9) / 2)


def test_time_by_name_counts_leaf_events_only(events):
    r = tr.reduce(events, chips=1, spans=SPANS)
    assert r["op_seconds"]["fusion.1"] == pytest.approx(140e-9)
    assert r["op_seconds"]["self_attention.3"] == pytest.approx(160e-9)
    assert "while.7" not in r["op_seconds"]  # its body did the work
    assert r["op_counts"] == {"fusion.1": 2, "self_attention.3": 2}
    # the ten longest are grouped by name without XLA's numbering
    assert r["top_ops"] == [["self_attention", pytest.approx(160e-9)],
                            ["fusion", pytest.approx(140e-9)]]
    # copies on the async line overlap the ops and are not busy time
    assert "copy-start.4" not in r["op_seconds"]
    # the other lines of the device plane are not ops
    assert "jit_step" not in r["op_seconds"]


def test_neighbours_that_overlap_by_rounding_are_not_nested():
    mk = lambda name, s, d: {"plane": "/device:TPU:0", "line": "XLA Ops",
                             "name": name, "start_ns": s, "dur_ns": d}
    # b starts half a nanosecond before a ends: neighbours, both leaves;
    # c lies inside b: b is not a leaf
    evs = [mk("a", 0.0, 100.5), mk("b", 100.0, 100.0), mk("c", 120.0, 50.0)]
    r = tr.reduce(evs, chips=1, spans=())
    assert r["op_seconds"] == {"a": pytest.approx(100.5e-9),
                               "c": pytest.approx(50e-9)}
    assert r["busy_s"] == pytest.approx(200e-9)


def test_gaps_are_attributed_to_the_span_they_fall_in(events):
    r = tr.reduce(events, chips=1, spans=SPANS)
    gaps = dict(r["idle_gaps"])
    # [100,150): fetch_batch covers 50 of it. [250,400): step covers
    # [250,380) = 130, fetch_loss 20. [500,520): fetch_loss.
    assert gaps["fetch_batch"] == pytest.approx(50e-9)
    assert gaps["step"] == pytest.approx(150e-9)
    assert gaps["fetch_loss"] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx(220e-9)
    assert r["idle_gaps"][0][0] == "step"  # longest first
    assert r["span_seconds"]["step"] == pytest.approx(220e-9)
    assert "unrelated_host_event" not in r["span_seconds"]


def test_gap_outside_every_span_is_said_so(events):
    r = tr.reduce(events, chips=1, spans=("fetch_batch",))
    gaps = dict(r["idle_gaps"])
    assert gaps["fetch_batch"] == pytest.approx(50e-9)
    assert gaps["(no span)"] == pytest.approx(150e-9)


def test_no_device_plane_is_an_error_on_the_chip(events):
    host_only = [e for e in events if e["plane"].startswith("/host")]
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce(host_only, chips=1, spans=SPANS)
    r = tr.reduce(host_only, chips=1, spans=SPANS, device_required=False)
    assert r["busy_s"] is None and r["op_seconds"] == {}
    assert r["window_s"] == pytest.approx(430e-9)


def test_hlo_text_is_split_into_a_name_and_what_tells_kernels_apart():
    raw = ('%self_attention.109 = (bf16[2,64,1024,64]{3,2,1,0:T(8,128)(2,1)}, '
           'bf16[2,64,1024,64]{3,2,1,0}) custom-call(bf16[2,64,1024,64] '
           '%bitcast.2586), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={}')
    name, stats = tr.split_hlo(raw)
    assert name == "self_attention.109"
    assert stats["custom_call_target"] == "tpu_custom_call"
    assert stats["result"].startswith("(bf16[2,64,1024,64]")
    assert tr.split_hlo("step") == ("step", {})
    assert tr.base_name("fusion.895") == "fusion"
    assert tr.base_name("exponential_reduce_fusion") == (
        "exponential_reduce_fusion")


def test_flash_roofline_reader_on_the_recording(events):
    """Two kernel events = 2/(3*2) of a step of a 2-layer model; 160 ns of
    device time against the least time for that much attention."""
    import types

    flops = load("flops.py")
    reader = load("layer_metrics/flash_attn_roofline.py")
    peaks = flops.peaks_for("TPU v5 lite")
    ctx = types.SimpleNamespace(
        reduction=tr.reduce(events, chips=1, spans=SPANS), peaks=peaks,
        config={"n_layer": 2, "n_head": 4, "n_embd": 64},
        cell={"global_batch": 4, "seq_len": 32})
    ops, nbytes = flops.attention_train_cost(4, 4, 32, 16, 2)
    least, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert reader.read(ctx) == pytest.approx(
        100.0 * least * (2 / 6.0) / 160e-9)
    ctx.peaks = None  # no chip, no share of a roofline
    assert reader.read(ctx) is None
    ctx.peaks, ctx.reduction = peaks, tr.reduce(
        [e for e in events if e["name"] != "self_attention.3"], chips=1,
        spans=SPANS)
    assert reader.read(ctx) is None  # nothing to read is not 0
