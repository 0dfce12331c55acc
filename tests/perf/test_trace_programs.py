"""``perf/trace_reduce.py`` books each program's device ops by that
program's own compiled text. A hand-made trace of two programs whose
instruction names collide (nanoseconds; as on a TPU, an op's event names no
program: the run on the ``XLA Modules`` line that holds it is its
program's):

  chip 0, "XLA Modules":  jit_prefill(11) [0,100)  jit_decode(22) [100,400)
      jit_decode(22) [500,800)
  chip 0, "XLA Ops":  in the prefill fusion.5 [0,90); in the first decode
      fusion.5 [100,200) and copy.3 [200,390); in the second decode
      fusion.5 [500,700) and custom-call.9 [700,790)
  chip 1: jit_decode(22) [0,300) holding fusion.5 [0,300)

The decode program's text places fusion.5 under the scope ``moe_experts``
and copy.3 under none; custom-call.9 is no instruction of it. So on chip 0
the decode program ran twice with 300 ns under ``moe_experts``, 190 under
its phase alone and 90 unattributed, and the prefill's fusion.5, which the
decode text names too, is booked nowhere: the prefill hands out no text.
"""

import collections
import json
import os

import pytest

from _bench import FIXTURES, load

tr = load("trace_reduce.py")
Scope = collections.namedtuple("Scope", "part scope")
DECODE = {"fusion.5": Scope("jit(decode)", "moe_experts"),
          "copy.3": Scope("jit(decode)", "")}


def _ev(line, name, start, end, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(end - start)}


@pytest.fixture(scope="module")
def events():
    m, o, p, d = "XLA Modules", "XLA Ops", "jit_prefill(11)", "jit_decode(22)"
    return [
        _ev(m, p, 0, 100), _ev(m, d, 100, 400), _ev(m, d, 500, 800),
        _ev(o, "fusion.5", 0, 90), _ev(o, "fusion.5", 100, 200),
        _ev(o, "copy.3", 200, 390), _ev(o, "fusion.5", 500, 700),
        _ev(o, "custom-call.9", 700, 790),
        _ev(m, d, 0, 300, plane="/device:TPU:1"),
        _ev(o, "fusion.5", 0, 300, plane="/device:TPU:1"),
        _ev("python", "tick", 0, 800, plane="/host:CPU"),
    ]


def test_an_op_belongs_to_the_run_that_holds_it(events):
    starts, runs = tr.module_line(events, "/device:TPU:0")
    assert runs == [(0.0, 100.0, "jit_prefill"), (100.0, 400.0, "jit_decode"),
                    (500.0, 800.0, "jit_decode")]
    assert tr.program_at(starts, runs, 0.0) == "jit_prefill"
    assert tr.program_at(starts, runs, 100.0) == "jit_decode"
    # an op that starts a rounding error before its run is still its own
    assert tr.program_at(starts, runs, 499.5) == "jit_decode"
    assert tr.program_at(starts, runs, 450.0) is None
    assert tr.program_at(starts, runs, 800.0) is None
    assert tr.program_at([], [], 10.0) is None


def test_each_op_goes_by_its_own_programs_map(events):
    r = tr.reduce(events, chips=1, spans=("tick",),
                  program_scopes={"jit_decode": DECODE})
    assert r["programs"] == {
        "jit_decode": {
            "runs": 2.0,
            "phase_seconds": {"jit(decode)": pytest.approx(490e-9),
                              "(unattributed)": pytest.approx(90e-9)},
            "scope_seconds": {"moe_experts": pytest.approx(300e-9)}},
        # a program without a map gives nothing: its fusion.5 is not the
        # decode program's, though the decode text names one
        "jit_prefill": {"runs": 1.0, "phase_seconds": {},
                        "scope_seconds": {}}}
    # the step's tables say nothing of the programs'
    assert r["phase_seconds"] == {} and r["scope_seconds"] == {}


def test_a_map_for_each_program_keeps_them_apart(events):
    prefill = {"fusion.5": Scope("jit(prefill)", "")}
    r = tr.reduce(events, chips=1, spans=("tick",),
                  program_scopes={"jit_decode": DECODE,
                                  "jit_prefill": prefill})
    assert r["programs"]["jit_prefill"]["phase_seconds"] == {
        "jit(prefill)": pytest.approx(90e-9)}
    assert r["programs"]["jit_decode"]["scope_seconds"] == {
        "moe_experts": pytest.approx(300e-9)}


def test_per_run_ms_is_a_chips_and_a_run_of_that_program(events):
    r = tr.reduce(events, chips=2, spans=("tick",),
                  program_scopes={"jit_decode": DECODE})
    # 300 + 300 ns on two chips in 3 runs: 1.5 a chip
    assert r["programs"]["jit_decode"]["runs"] == 1.5
    assert tr.per_run_ms(r, "jit_decode", "scope_seconds",
                         ("moe_experts",)) == pytest.approx(
        1e3 * 600e-9 / 2 / 1.5)
    assert tr.per_run_ms(r, "jit_decode", "scope_seconds",
                         ("moe_route",)) is None
    assert tr.per_run_ms(r, "jit_prefill", "phase_seconds",
                         ("jit(prefill)",)) is None
    assert tr.per_run_ms(r, "jit_verify", "phase_seconds",
                         ("jit(verify)",)) is None
    assert tr.per_run_ms(None, "jit_decode", "scope_seconds",
                         ("moe_experts",)) is None
    one = tr.reduce(events, chips=1, spans=("tick",), scopes=DECODE)
    assert tr.per_run_ms(one, "jit_decode", "scope_seconds",
                         ("moe_experts",)) is None


def test_the_programs_add_nothing_else_to_the_reduction(events):
    plain = tr.reduce(events, chips=2, spans=("tick",))
    booked = tr.reduce(events, chips=2, spans=("tick",),
                       program_scopes={"jit_decode": DECODE})
    assert set(booked) == set(plain) | {"programs"}
    assert {k: v for k, v in booked.items() if k != "programs"} == plain
    host = [e for e in events if e["plane"].startswith("/host")]
    assert tr.reduce(host, spans=("tick",), device_required=False,
                     program_scopes={"jit_decode": DECODE})["programs"] == {}


def test_one_text_gives_the_recorded_reduction_as_before():
    """The recorded fixture's reduction, by one program's map or none, is
    what the reduction gave before programs were booked apart
    (``fixtures/reduction_one_text.json``), every key and every float."""
    scopes = {
        "while.7": Scope("forward", ""), "fusion.1": Scope("forward", ""),
        "self_attention.3": Scope("forward", ""),
        "fusion.9": Scope("backward", "moe_combine"),
        "flash_bwd_dq.7": Scope("backward", ""),
        "mla_rope.2": Scope("backward", "mla_project"),
        "gmm.4": Scope("backward", "moe_experts"),
        "all-reduce.1": Scope("grad_sync", ""),
    }
    with open(os.path.join(FIXTURES, "trace_events.json")) as f:
        events = json.load(f)
    with open(os.path.join(FIXTURES, "reduction_one_text.json")) as f:
        recorded = json.load(f)
    assert len(recorded) == 4
    for case in recorded:
        r = tr.reduce(events, chips=case["chips"],
                      spans=("fetch_batch", "step", "fetch_loss"),
                      scopes=scopes if case["scoped"] else None)
        assert json.loads(json.dumps(r)) == case["reduction"]
