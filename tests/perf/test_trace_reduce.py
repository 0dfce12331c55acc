"""perf/trace_reduce.py on a small recorded trace, against hand-worked
numbers. The recording (fixtures/trace_events.json, nanoseconds):

  chip 0, line "XLA Ops":  while.7 [0,100) holding fusion.1 [0,40) and
      self_attention.3 [40,100); fusion.1 [150,250); self_attention.3 [400,500)
      (both self_attention.3 carry kernel_metadata: flash_fwd)
  chip 1, line "XLA Ops":  fusion.9 [0,250) holding flash_bwd_dq.7 [0,80)
      (kernel flash_bwd_dq; itself holding custom-call.5 [10,11)), mla_rope.2
      [80,100) (kernel mla_rope), gmm.4 [100,130) (a Mosaic call that names
      no kernel) and all-reduce.1 [200,240)
  line "XLA Modules":  jit_step(77) twice on each chip; on chip 0 also
      jit_convert_element_type(5) three times, a nanosecond each
  host spans:  fetch_batch [90,160)  step [160,380)  fetch_loss [380,520)

so on chip 0 the device is busy over [0,100) + [150,250) + [400,500) = 300 ns
of a window [0,520), and idle over [100,150), [250,400) and [500,520); chip 1
is busy 250 ns, of which fusion.9 itself has 250 - 80 - 20 - 30 - 40 = 80.
"""

import collections

import json
import os
import random
import time

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


def test_time_by_name_is_self_time(events):
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
    # b starts half a nanosecond before a ends: neighbours, neither inside
    # the other; c lies inside b, which keeps what c does not cover
    evs = [mk("a", 0.0, 100.5), mk("b", 100.0, 100.0), mk("c", 120.0, 50.0)]
    r = tr.reduce(evs, chips=1, spans=())
    assert r["op_seconds"] == {"a": pytest.approx(100.5e-9),
                               "b": pytest.approx(50e-9),
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


# -- idle gaps by host span: the sweep against the plain double loop ----------


def loop_idle_gaps(events, spans, chips=1):
    """``reduce``'s ``idle_gaps`` as the plain double loop over gaps and host
    spans computes them: each gap of the first chip to the span that
    overlaps it most (the first in the events' order of those that tie),
    seconds added in gap order, longest first. The reference the sweep is
    held to with ``==``."""
    planes = sorted({e["plane"] for e in events
                     if tr.DEVICE_PLANE.match(e["plane"])},
                    key=lambda p: int(tr.DEVICE_PLANE.match(p).group(1)))
    planes = planes[:chips]
    host = [e for e in events if e["plane"].startswith("/host:")
            and e["name"] in set(spans)]
    dev = [e for e in events if e["plane"] in planes
           and e["line"] == tr.OPS_LINE]
    t0 = min(e["start_ns"] for e in host + dev)
    t1 = max(e["start_ns"] + e["dur_ns"] for e in host + dev)
    merged = tr.union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                       for e in dev if e["plane"] == planes[0]])
    gaps, cursor = [], t0
    for s, e in merged + [[t1, t1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    by_span = collections.Counter()
    for gs, ge in gaps:
        best, best_ov = "(no span)", 0.0
        for h in host:
            ov = min(ge, h["start_ns"] + h["dur_ns"]) - max(gs, h["start_ns"])
            if ov > best_ov:
                best, best_ov = h["name"], ov
        by_span[best] += (ge - gs) * 1e-9
    return [[k, v] for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])]


def _op(name, start, dur, chip=0):
    return {"plane": f"/device:TPU:{chip}", "line": "XLA Ops", "name": name,
            "start_ns": start, "dur_ns": dur}


def _span(name, start, dur, line="python"):
    return {"plane": "/host:CPU", "line": line, "name": name,
            "start_ns": start, "dur_ns": dur}


SERVE_SPANS = ("submit", "tick", "observe", "wait")


def synthetic_trace(seed, ticks, ops_per_tick=40, gap_share=0.3, grid=None):
    """A serving loop's trace from ``seed``: per tick the host spans
    ``submit``, ``tick`` (the device's ops run inside it, some nested, a gap
    after ``gap_share`` of them), ``observe`` and now and then ``wait``;
    besides, stray spans of those names and of others, short and long, that
    nest in, overlap and straddle the rest, on two host lines, and a few ops
    on a second chip. ``grid`` rounds every time to its multiples, so that
    overlaps tie exactly."""
    rng = random.Random(seed)
    q = (lambda x: round(x / grid) * grid) if grid else (lambda x: x)
    events, t = [], q(rng.uniform(0.0, 500.0))
    for k in range(ticks):
        dur = q(rng.uniform(5.0, 30.0))
        events.append(_span("submit", t, dur))
        t += dur
        began, d = t, t + q(rng.uniform(10.0, 50.0))
        for i in range(ops_per_tick):
            dur = q(rng.uniform(1.0, 20.0))
            events.append(_op(f"fusion.{i}", d, dur))
            if rng.random() < 0.2:
                events.append(_op(f"copy.{i}", d + q(dur / 4), q(dur / 2)))
            d += dur
            if rng.random() < gap_share:
                d += q(rng.uniform(0.5, 30.0))
        t = d + q(rng.uniform(5.0, 40.0))
        events.append(_span("tick", began, t - began))
        if k % 7 == 0:
            events.append(_op("all-reduce.1", began, t - began, chip=1))
        dur = q(rng.uniform(2.0, 20.0))
        events.append(_span("observe", t, dur))
        t += dur
        if rng.random() < 0.3:
            dur = q(rng.uniform(50.0, 400.0))
            events.append(_span("wait", t, dur))
            t += dur
        for _ in range(rng.randrange(3)):
            events.append(_span(
                rng.choice(SERVE_SPANS + ("unrelated",)),
                q(began + rng.uniform(-300.0, 600.0)),
                q(rng.choice((0.0, rng.uniform(1.0, 60.0),
                              rng.uniform(100.0, 3000.0)))),
                line=rng.choice(("python", "runtime"))))
    return events


@pytest.mark.parametrize("chips", [1, 2])
@pytest.mark.parametrize("spans", [SPANS, ("fetch_batch",), ()])
def test_idle_gaps_equal_the_double_loop_on_the_recording(events, chips,
                                                          spans):
    assert tr.reduce(events, chips=chips, spans=spans)["idle_gaps"] == (
        loop_idle_gaps(events, spans, chips))


#: device ops on chip 0 at [0,100) and [300,400), so one gap [100,300),
#: and each case's host spans around it
GAP_CASES = {
    # spans inside spans: the outer one overlaps most
    "nested": [_span("step", 50.0, 300.0), _span("fetch_batch", 120.0, 80.0),
               _span("fetch_loss", 210.0, 80.0)],
    # a chain of partial overlaps: the middle one overlaps most
    "overlapping": [_span("fetch_batch", 80.0, 100.0),
                    _span("step", 150.0, 110.0),
                    _span("fetch_loss", 240.0, 90.0)],
    # one span across each end of the gap, 20 ns of it each: a tie
    "straddling": [_span("fetch_batch", 50.0, 70.0),
                   _span("fetch_loss", 280.0, 80.0)],
    # two spans over the same stretch: a tie
    "tied": [_span("step", 150.0, 100.0), _span("fetch_loss", 150.0, 100.0),
             _span("fetch_batch", 160.0, 10.0)],
    # spans that touch the gap's ends or last no time: no overlap at all
    "touching": [_span("step", 0.0, 100.0), _span("fetch_loss", 300.0, 50.0),
                 _span("fetch_batch", 200.0, 0.0)],
    # before the first op and after the last: gaps at both ends of the window
    "before_and_after": [_span("fetch_batch", -80.0, 60.0),
                         _span("fetch_loss", 420.0, 40.0),
                         _span("step", 390.0, 100.0)],
    # long spans that hold every gap, beside short ones
    "enclosing": [_span("step", -50.0, 600.0), _span("fetch_batch", 110.0,
                                                     5.0),
                  _span("fetch_loss", -40.0, 590.0)],
    "no_host_span": [],
}


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("case", sorted(GAP_CASES))
def test_idle_gaps_equal_the_double_loop_in_each_case(case, reverse):
    host = GAP_CASES[case][::-1] if reverse else GAP_CASES[case]
    evs = [_op("fusion.1", 0.0, 100.0), _op("fusion.2", 300.0, 100.0),
           _span("unrelated", 100.0, 200.0)] + host
    got = tr.reduce(evs, chips=1, spans=SPANS)["idle_gaps"]
    assert got == loop_idle_gaps(evs, SPANS)
    if case == "tied":  # the first of the two in the events' order
        assert got[0][0] == ("fetch_loss" if reverse else "step")
    if case == "touching":
        assert got == [["(no span)", pytest.approx(200e-9)]]


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("grid", [None, 5.0])
@pytest.mark.parametrize("seed", range(6))
def test_idle_gaps_equal_the_double_loop_on_synthetic_traces(seed, grid,
                                                             reverse):
    evs = synthetic_trace(seed, ticks=30, grid=grid)
    if reverse:
        evs = evs[::-1]
    for spans in (SERVE_SPANS, ("tick", "wait")):
        got = tr.reduce(evs, chips=2, spans=spans)["idle_gaps"]
        assert got == loop_idle_gaps(evs, spans), spans
    assert {k for k, _ in got} == {"tick", "wait"}  # both win gaps


def test_idle_gaps_of_a_long_slice_take_a_sweep_not_a_square():
    """About a chat cell's 5 s slice: 600 ticks, 24,674 gaps against some
    2,700 host spans. The double loop takes about 20 s here on one CPU core
    (and grows with the square of the ticks), the sweep under one."""
    evs = synthetic_trace(7, ticks=600, ops_per_tick=100, gap_share=0.4)
    host = [e for e in evs if e["plane"].startswith("/host:")
            and e["name"] in SERVE_SPANS]
    t0 = time.perf_counter()
    r = tr.reduce(evs, chips=1, spans=SERVE_SPANS)
    took = time.perf_counter() - t0
    assert len(host) > 2400
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert took < 10.0, f"{took:.1f} s"


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
    """Two runs of the step's program; 160 ns of device time in kernels the
    program named ``flash_fwd`` against the least time for two steps'
    attention."""
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
    assert reader.read(ctx) == pytest.approx(100.0 * least * 2 / 160e-9)
    ctx.peaks = None  # no chip, no share of a roofline
    assert reader.read(ctx) is None
    ctx.peaks, ctx.reduction = peaks, tr.reduce(
        [e for e in events if e["name"] != "self_attention.3"], chips=1,
        spans=SPANS)
    assert reader.read(ctx) is None  # nothing to read is not 0


# -- what names a kernel and a phase ------------------------------------------

Scope = collections.namedtuple("Scope", "part scope")
#: instruction name -> where the compiled step's text would place it
SCOPES = {
    "while.7": Scope("forward", ""), "fusion.1": Scope("forward", ""),
    "self_attention.3": Scope("forward", ""),
    "fusion.9": Scope("backward", "moe_combine"),
    "flash_bwd_dq.7": Scope("backward", ""),
    "mla_rope.2": Scope("backward", "mla_project"),
    "gmm.4": Scope("backward", "moe_experts"),
    "all-reduce.1": Scope("grad_sync", ""),
}


def test_split_hlo_keeps_what_the_program_called_the_kernel():
    raw = ('%self_attention.3 = (bf16[8,1024,1024]{2,1,0}, f32[128,1,1024]) '
           'custom-call(bf16[128,1024,64]{2,1,0} %bitcast.1), '
           'custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={bf16[128,1024,64]{2,1,0}}, '
           'frontend_attributes={kernel_metadata={\n"kernel":"flash_fwd",\n'
           '"block_q":"1024",\n"block_k":"512",\n"d_qk":"64",\n"d_v":"64"\n}}')
    name, stats = tr.split_hlo(raw)
    assert name == "self_attention.3"
    assert stats["custom_call_target"] == "tpu_custom_call"
    assert stats["kernel_metadata"] == {
        "kernel": "flash_fwd", "block_q": "1024", "block_k": "512",
        "d_qk": "64", "d_v": "64"}
    # the library's grouped matmuls print an empty one
    _, stats = tr.split_hlo('%gmm.4 = bf16[8,8] custom-call(), custom_call_'
                            'target="tpu_custom_call", frontend_attributes='
                            '{kernel_metadata={}}')
    assert stats["kernel_metadata"] == {}
    assert "kernel_metadata" not in tr.split_hlo(
        "%fusion.1 = bf16[8] fusion(bf16[8] %p), kind=kLoop")[1]


@pytest.mark.parametrize("chips", [1, 2])
def test_steps_are_the_runs_of_the_program_that_took_the_time(events, chips):
    # jit_convert_element_type ran three times on chip 0, for 3 ns in all
    assert tr.reduce(events, chips=chips, spans=SPANS)["steps"] == 2.0


def test_steps_without_a_modules_line_are_nought(events):
    ops = [e for e in events if e["line"] != "XLA Modules"]
    assert tr.reduce(ops, chips=1, spans=SPANS)["steps"] == 0.0
    host_only = [e for e in events if e["plane"].startswith("/host")]
    r = tr.reduce(host_only, chips=1, spans=SPANS, device_required=False)
    assert r["steps"] == 0.0 and r["kernel_seconds"] == {}
    assert r["phase_seconds"] == {} and r["scope_seconds"] == {}


def test_time_by_kernel_goes_by_the_programs_name(events):
    r = tr.reduce(events, chips=2, spans=SPANS)
    assert r["kernel_seconds"] == {
        "flash_fwd": pytest.approx(160e-9),      # named self_attention.3
        "flash_bwd_dq": pytest.approx(79e-9),    # 80 less what nests in it
        "mla_rope": pytest.approx(20e-9),
        "gmm": pytest.approx(30e-9)}             # by instruction name
    assert r["kernel_counts"] == {"flash_fwd": 2, "flash_bwd_dq": 1,
                                  "mla_rope": 1, "gmm": 1}
    # a parent keeps what its children do not cover
    assert r["op_seconds"]["fusion.9"] == pytest.approx(80e-9)
    assert r["op_seconds"]["custom-call.5"] == pytest.approx(1e-9)
    # without the compiled step's text nothing is said of phases
    assert r["phase_seconds"] == {} and r["scope_seconds"] == {}


@pytest.mark.parametrize("chips", [1, 2])
def test_phases_add_up_to_busy_time(events, chips):
    r = tr.reduce(events, chips=chips, spans=SPANS, scopes=SCOPES)
    assert sum(r["phase_seconds"].values()) == pytest.approx(
        r["busy_s"] * chips)
    assert sum(r["op_seconds"].values()) == pytest.approx(
        r["busy_s"] * chips)
    if chips == 1:
        assert r["phase_seconds"] == {"forward": pytest.approx(300e-9)}
        assert r["scope_seconds"] == {}
    else:
        # custom-call.5 is no instruction of the text given
        assert r["phase_seconds"] == {
            "forward": pytest.approx(300e-9),
            "backward": pytest.approx(209e-9),
            "grad_sync": pytest.approx(40e-9),
            "(unattributed)": pytest.approx(1e-9)}
        assert r["scope_seconds"] == {
            "moe_combine": pytest.approx(80e-9),
            "mla_project": pytest.approx(20e-9),
            "moe_experts": pytest.approx(30e-9)}


def test_per_step_ms_is_a_chips_and_a_steps(events):
    r = tr.reduce(events, chips=2, spans=SPANS, scopes=SCOPES)
    # 209 ns on two chips in two steps
    assert tr.per_step_ms(r, "phase_seconds", ("backward",)) == (
        pytest.approx(1e3 * 209e-9 / 2 / 2))
    assert tr.per_step_ms(r, "phase_seconds", ("unscale", "guard")) is None
    assert tr.per_step_ms(r, "scope_seconds", ("moe_route", "moe_combine")
                          ) == pytest.approx(1e3 * 80e-9 / 2 / 2)
    assert tr.per_step_ms(None, "phase_seconds", ("forward",)) is None
    no_steps = dict(r, steps=0.0)
    assert tr.per_step_ms(no_steps, "phase_seconds", ("forward",)) is None


def _ctx(events, chips, **kw):
    import types

    flops = load("flops.py")
    return types.SimpleNamespace(
        reduction=tr.reduce(events, chips=chips, spans=SPANS, scopes=SCOPES),
        peaks=flops.peaks_for("TPU v5 lite"), counters={}, chips=chips,
        config={"n_layer": 2, "n_head": 4, "n_embd": 64},
        cell={"global_batch": 4, "seq_len": 32}, **kw)


def test_rooflines_read_the_same_whatever_the_instruction_is_called(events):
    """``name=`` on the flash ``pallas_call``s renames the instructions; the
    readers go by ``kernel_metadata`` and read what they read. A foreign
    kernel (``mla_rope``) in the module is no flash call."""
    reader = load("layer_metrics/flash_attn_roofline.py")
    before = reader.read(_ctx(events, 2))
    renamed = [dict(e, name={"self_attention.3": "flash_fwd.3",
                             "flash_bwd_dq.7": "self_attention.9"}.get(
                                 e["name"], e["name"])) for e in events]
    assert reader.read(_ctx(renamed, 2)) == pytest.approx(before)
    without_rope = [e for e in events if e["name"] != "mla_rope.2"]
    assert reader.read(_ctx(without_rope, 2)) == pytest.approx(before)
    flops = load("flops.py")
    ops, nbytes = flops.attention_train_cost(4, 4, 32, 16, 2)
    least, _ = flops.roofline_seconds(ops, nbytes, flops.peaks_for(
        "TPU v5 lite"))
    # 160 ns of flash_fwd + 79 ns of flash_bwd_dq, two steps
    assert before == pytest.approx(100.0 * least * 2 / 239e-9)


@pytest.mark.parametrize("metric,kernel,secs", [
    ("flash_fwd_roofline", "flash_fwd", 160e-9),
    ("flash_bwd_dq_roofline", "flash_bwd_dq", 79e-9),
])
def test_each_flash_kernel_has_a_roofline_of_its_own(events, metric, kernel,
                                                     secs):
    flops = load("flops.py")
    reader = load(f"layer_metrics/{metric}.py")
    ops, nbytes = flops.flash_kernel_costs(4, 4, 32, 16, 16, 2)[kernel]
    least, _ = flops.roofline_seconds(ops, nbytes, flops.peaks_for(
        "TPU v5 lite"))
    assert reader.read(_ctx(events, 2)) == pytest.approx(
        100.0 * least * 2 / secs)
    ctx = _ctx(events, 2)
    ctx.peaks = None  # no chip, no share of a roofline
    assert reader.read(ctx) is None


def test_a_kernel_the_trace_does_not_hold_reads_nothing(events):
    reader = load("layer_metrics/flash_bwd_dkv_roofline.py")
    assert reader.read(_ctx(events, 2)) is None  # nothing to read is not 0
    ctx = _ctx(events, 2)
    ctx.config = {"hidden_size": 64}  # neither GPT-2's keys nor latent's
    assert load("layer_metrics/flash_fwd_roofline.py").read(ctx) is None


@pytest.mark.parametrize("metric,table,names,ns", [
    ("step_forward_ms.train", "phase", ("forward",), 300),
    ("step_backward_ms.train", "phase", ("backward",), 209),
    ("step_grad_sync_ms.train", "phase", ("grad_sync",), 40),
    ("step_optimizer_ms.train", "phase", ("optimizer",), None),
    ("step_guard_ms.train", "phase", ("unscale", "guard"), None),
    ("moe_routed_path_ms.train", "scope", ("moe_combine",), 80),
    ("mla_project_ms.train", "scope", ("mla_project",), 20),
])
def test_phase_and_scope_readers_on_the_recording(events, metric, table,
                                                  names, ns):
    value = load(f"layer_metrics/{metric}.py").read(_ctx(events, 2))
    if ns is None:
        assert value is None  # the recording holds no such phase
    else:
        assert value == pytest.approx(1e3 * ns * 1e-9 / 2 / 2)
    bare = _ctx(events, 2)
    bare.reduction = tr.reduce(events, chips=2, spans=SPANS)  # no text
    assert load(f"layer_metrics/{metric}.py").read(bare) is None


def test_grouped_matmuls_go_by_instruction_name_and_the_counted_rows(events):
    joyai = load("joyai_flops.py")
    flops = load("flops.py")
    cfg = {"layers_kept": 2, "num_nextn_predict_layers": 1,
           "first_k_dense_replace": 1, "hidden_size": 64,
           "moe_intermediate_size": 32, "n_routed_experts": 4}
    ctx = _ctx(events, 2)
    ctx.config, ctx.counters = cfg, {"moe_rows_here_per_step": 512.0}
    ops, nbytes = joyai.experts_train_cost(cfg, 512.0, 2)
    least, _ = flops.roofline_seconds(ops, nbytes, ctx.peaks)
    reader = load("layer_metrics/moe_experts_roofline.py")
    assert reader.read(ctx) == pytest.approx(100.0 * least * 2 / 30e-9)
    ctx.counters = {}
    assert reader.read(ctx) is None


def test_the_compact_share_is_the_programs_counter():
    import types

    reader = load("layer_metrics/moe_compact_share.train.py")
    assert reader.read(types.SimpleNamespace(
        counters={"moe_compact_share": 0.875})) == 0.875
    assert reader.read(types.SimpleNamespace(counters={})) is None
