"""The driver through ``perf/run.py``'s own code, at a tiny fixture
configuration on the CPU (two GPT layers of width 64), and the proofs that
the output check can fail: the control in a lower precision, and the timed
path broken underneath. A fixture cell and a fixture metric are registered
from a temporary directory: ``perf/run.py`` is not edited for them.

Nothing here is a device number: the result line says ``cpu``.
"""

import io
import json
import time

import pytest

from _bench import GPT_TINY, PRETRAIN_TINY, e2e, fixture_root, layer, load

run = load("run.py", name="perf_test_run")
LIMIT_S = 55.0  # each test's own limit, well under a minute

KEYS = ["correct", "attempted", "failed", "metrics", "device"]
TRAIN_METRICS = [e2e("setup_s", "s"), e2e("train_step_ms", "ms")]


def _run(root, cell, seed=2**31 + 5, seconds=0.3, trace=0):
    t0 = time.perf_counter()
    out = io.StringIO()
    line = run.run_cell(root, cell, seed, seconds, trace, allow_cpu=True,
                        out=out)
    took = time.perf_counter() - t0
    assert took < LIMIT_S, f"{cell} took {took:.0f} s"
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(line))
    return line


def _shape_ok(line, metrics, trace):
    keys = list(line)
    assert keys[:5] == KEYS and keys[-1] == "compared"
    assert set(keys) - set(KEYS) <= {"breakdown", "compared"}
    assert ("breakdown" in line) == bool(trace)
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == set(metrics)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in line["compared"]:
        assert set(c) >= {"name", "value", "limit"}


@pytest.fixture
def train_root(tmp_path):
    return fixture_root(tmp_path, {"gpt_tiny.pretrain": PRETRAIN_TINY},
                        {"gpt_tiny": GPT_TINY}, TRAIN_METRICS)


def test_pretrain_driver_runs_and_is_correct(train_root):
    line = _run(train_root, "gpt_tiny.pretrain")
    _shape_ok(line, ["setup_s", "train_step_ms"], trace=0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    names = [c["name"] for c in line["compared"]]
    # step 1's and step 3's losses have no limit in the cell's file, and a
    # number without one is not compared
    assert names == ["loss_gap_step2", "grad_norm_gap", "update_norm_gap",
                     "skipped_updates", "compiles_in_window"]


def test_a_cell_and_a_metric_are_added_as_files_only(tmp_path):
    """A later PR's way in: a new cell file, a new metric reader and their
    BENCHMARK.json entries - no edit to perf/run.py or any file there."""
    cell = dict(PRETRAIN_TINY, micro_batch=1, global_batch=2)
    reader = ('"""Fixture: steps taken, from the driver\'s counters."""\n\n\n'
              'def read(ctx):\n    return ctx.counters.get("steps")\n')
    silent = ('"""Fixture: finds nothing to read."""\n\n\n'
              'def read(ctx):\n    return None\n')
    name = "gpt_tiny.pretrain_small_batch"
    root = fixture_root(
        tmp_path, {name: cell}, {"gpt_tiny": GPT_TINY},
        TRAIN_METRICS + [
            layer("steps.fixture", "1", "train_step_ms", [name]),
            layer("silent.fixture", "%", "train_step_ms", [name]),
            layer("flash_attn_roofline", "%", "train_step_ms", [name])],
        extra_files=[
            ("perf/layer_metrics/steps.fixture.py", reader),
            ("perf/layer_metrics/silent.fixture.py", silent)])
    line = _run(root, name, trace=1)
    # the traced run reports the per-layer metrics; a reader that found
    # nothing (no chip: no share of a roofline) is left out of the line,
    # never reported as 0
    _shape_ok(line, ["steps.fixture"], trace=1)
    assert line["metrics"]["steps.fixture"]["value"] == line["attempted"]
    assert line["correct"] is True


def test_a_slow_profiler_stop_stays_out_of_the_traced_window(
        tmp_path, monkeypatch):
    """Stopping the profiler takes seconds on the chip. It happens once the
    window has returned: ``window_s``, which every rate of a traced run
    divides by (``mfu_pct.train``), ends where a plain run's ends."""
    import jax

    real, stop_s = jax.profiler.stop_trace, 5.0

    def slow_stop():
        time.sleep(stop_s)
        real()

    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    readers = [(f"perf/layer_metrics/{name}.fixture.py",
                f'"""Fixture."""\n\n\ndef read(ctx):\n    return {what}\n')
               for name, what in (
                   ("window_s", 'ctx.counters["window_s"]'),
                   ("stop_s", "ctx.trace_stop_s"),
                   ("step_s", 'ctx.counters["window_s"] '
                              '/ ctx.counters["steps"]'))]
    cell = "gpt_tiny.pretrain"
    root = fixture_root(
        tmp_path, {cell: PRETRAIN_TINY}, {"gpt_tiny": GPT_TINY},
        TRAIN_METRICS + [layer(f"{n}.fixture", "s", "train_step_ms", [cell])
                         for n in ("window_s", "stop_s", "step_s")],
        extra_files=readers)
    seconds = 0.6
    traced = _run(root, cell, seconds=seconds, trace=1)["metrics"]
    assert traced["stop_s.fixture"]["value"] >= stop_s
    # the window is the --seconds and the step that was under way at their
    # end, not the five seconds after it
    assert seconds <= traced["window_s.fixture"]["value"] < seconds + 1.0
    plain = _run(root, cell, seconds=seconds, trace=0)["metrics"]
    assert traced["step_s.fixture"]["value"] < 2 * 1e-3 * plain[
        "train_step_ms"]["value"] + 0.05


def test_unknown_cell_and_no_accelerator_are_refused(train_root):
    with pytest.raises(run.Refused, match="no cell"):
        run.run_cell(train_root, "nope.cell", 1, 0.1, 0, allow_cpu=True)
    with pytest.raises(run.Refused, match="no accelerator"):
        run.run_cell(train_root, "gpt_tiny.pretrain", 1, 0.1, 0)


# -- the comparison has been shown to fail -----------------------------------


def _break_train_step(monkeypatch, how):
    """Plant a fault in the program's own step, under the driver."""
    import jax

    from apex_tpu import training

    real = training.build_gpt_training

    def broken(cfg):
        built = real(cfg)
        step = built.train_step

        def unchanged(*a):
            out = step(*a)
            # the step runs, and hands back the state it was given
            return tuple(a[:4]) + tuple(out[4:])

        def half_batch(*a):
            a = list(a)
            # half of the batch left out, the mean taken over the rest
            # (global batch 4 = 2 microbatches of 2 rows)
            a[5], a[6] = a[5][:1], a[6][:1]
            return step(*a)

        built.train_step = jax.jit(
            {"unchanged": unchanged, "half_batch": half_batch}[how])
        return built

    monkeypatch.setattr(training, "build_gpt_training", broken)


@pytest.mark.parametrize("how,caught_by", [
    ("unchanged", "update_norm_gap"),
    ("half_batch", "grad_norm_gap"),
])
def test_a_broken_training_step_reads_incorrect(train_root, monkeypatch,
                                                how, caught_by):
    _break_train_step(monkeypatch, how)
    line = _run(train_root, "gpt_tiny.pretrain")
    assert line["correct"] is False
    over = {c["name"] for c in line["compared"] if c["value"] > c["limit"]}
    assert caught_by in over, line["compared"]


def _bare_state(drv):
    """The driver's state as far as its ``reference`` reads it: no program
    is built, the reference stands in its place."""
    st = drv.State()
    st.cell, st.config = PRETRAIN_TINY, GPT_TINY
    st.heads, st.batch = 4, PRETRAIN_TINY["global_batch"]
    st.lr, st.weight_decay = 3e-4, 0.01
    st.dims = dict(layers=2, hidden=64, vocab=128, max_positions=32)
    return st


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_the_training_check(seed):
    """The reference in the nearest lower precision, put in the program's
    place, has to read incorrect by at least one number - at the same tiny
    limits the bf16 program passes (test_pretrain_driver_runs_and_is_
    correct)."""
    t0 = time.perf_counter()
    compare = load("compare.py")
    drv = load("drivers/gpt_pretrain.py")
    st = _bare_state(drv)
    want = drv.reference(st, seed)
    control = drv.reference(st, seed, precision="fp8")
    compared = compare.training(control, want, PRETRAIN_TINY["limits"])
    assert not compare.correct(compared), compared
    # and the reference agrees with itself
    again = compare.training(want, want, PRETRAIN_TINY["limits"])
    assert all(c["value"] == 0 for c in again) and compare.correct(again)
    assert time.perf_counter() - t0 < LIMIT_S


def test_study_judges_control_and_faults_through_the_cells_limits(
        train_root):
    """perf/study.py's readings go through the comparison a run uses, with
    the cell's limits: the program reads correct, the control and each
    planted fault do not."""
    t0 = time.perf_counter()
    compare = load("compare.py")
    drv = run.load_module(train_root, "drivers", "gpt_pretrain")
    ctx = run.Context(train_root, PRETRAIN_TINY, GPT_TINY, 0, 0.0, 0,
                      {"platform": "cpu"}, None)
    verdicts = {}
    for kind, seed, compared, readings in drv.study(
            PRETRAIN_TINY, GPT_TINY, [2], ctx, controls=1):
        assert seed == 2
        verdicts[kind] = compare.correct(compared)
        assert [c["limit"] for c in readings[:-1]] == [1e30] * 5
        assert len(compared) == 4  # three limits and the skipped updates
    assert verdicts == {"program": True, "control_fp8": False,
                        "fault_half_batch": False,
                        "fault_state_unchanged": False}
    assert time.perf_counter() - t0 < LIMIT_S
