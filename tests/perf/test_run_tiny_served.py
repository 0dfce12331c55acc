"""A second served model through ``perf/run.py``, beside ``gpt_tiny.chat``,
by new files alone: a fixture driver made of the hooks the one serving
window asks of a driver (``fixtures/gpt_rope_serve.py``: GPT-2 with rotary
positions, its own operations count and its own reference forward pass), a
configuration and a cell, in a fixture root. Both cells run the same window
and report the same ``tpot_p50_ms``; the rope model's tokens are held to its
own reference by the same served-gap arithmetic, which fails a planted
fault.

Nothing here is a device number: the result lines say ``cpu``.
"""

import glob
import io
import json
import os
import re

import pytest

from _bench import FIXTURES, PERF, e2e, fixture_root, layer, load
from test_run_tiny_chat import CHAT_TINY, GPT_TINY_SERVED

run = load("run.py", name="perf_test_run_served")

ROPE = "gpt_rope_tiny.chat"
CELLS = ["gpt_tiny.chat", ROPE]
ROPE_CHAT = dict(
    CHAT_TINY, config="gpt_rope_tiny", driver="gpt_rope_serve",
    # this fixture's own readings on the CPU: the bf16 engine reads at most
    # 0.013 over 17 seeds, the fp8 control 0.067 / 0.193 / 0.214 (and 0 on
    # one seed: another number's to catch), a stale position 0.038-0.55,
    # an altered token 6.0-8.3
    limits={"served_gap_max": 0.03})
SERVED = [e2e("setup_s", "s"), dict(e2e("tpot_p50_ms", "ms"),
                                    workloads=CELLS)]
READERS = [layer("mfu_pct.serve", "%", "tpot_p50_ms", CELLS),
           layer("kv_pool_held_share.serve", "share", "tpot_p50_ms", CELLS),
           layer("decode_keys_read.serve", "share", "tpot_p50_ms", [ROPE])]
#: a reader of a counter the rope driver adds to the window's (``counters``)
KEYS_READ = '''"""Share of the lanes' positions decode attention read."""


def read(ctx):
    return ctx.counters.get("decode_keys_read_share") or None
'''


@pytest.fixture
def served_root(tmp_path):
    with open(os.path.join(FIXTURES, "gpt_rope_serve.py")) as f:
        driver = f.read()
    return fixture_root(
        tmp_path, {"gpt_tiny.chat": CHAT_TINY, ROPE: ROPE_CHAT},
        {"gpt_tiny": GPT_TINY_SERVED,
         "gpt_rope_tiny": dict(GPT_TINY_SERVED, name="gpt_rope_tiny")},
        SERVED + READERS,
        extra_files=[("perf/drivers/gpt_rope_serve.py", driver),
                     ("perf/layer_metrics/decode_keys_read.serve.py",
                      KEYS_READ)])


def _run(root, cell, seed=2**31 + 5, trace=0):
    out = io.StringIO()
    line = run.run_cell(root, cell, seed, 0.8, trace, allow_cpu=True,
                        out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == (
        json.loads(json.dumps(line)))
    assert line["device"]["platform"] == "cpu"
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_two_served_models_run_through_one_window(served_root, cell):
    line = _run(served_root, cell)
    assert set(line["metrics"]) == {"setup_s", "tpot_p50_ms"}
    assert line["metrics"]["tpot_p50_ms"]["value"] > 0
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] == 13 and line["failed"] == 0
    assert [c["name"] for c in line["compared"]] == [
        "served_gap_max", "requests_never_answered",
        "steady_state_compiles", "compiles_in_window"]


def test_the_second_model_reports_the_windows_counters(served_root):
    """A traced run of the rope cell: the readers of the window's counters
    read it as they read the chat cell (the mfu reader has no peak on a
    CPU and stays out of the line, never 0), and a counter the driver adds
    to the window's (its ``counters`` hook) reaches its reader."""
    line = _run(served_root, ROPE, seed=2**31 + 9, trace=1)
    assert set(line["metrics"]) == {"kv_pool_held_share.serve",
                                    "decode_keys_read.serve"}
    assert 0 < line["metrics"]["kv_pool_held_share.serve"]["value"] <= 1
    assert 0 < line["metrics"]["decode_keys_read.serve"]["value"] <= 1
    assert line["correct"] is True, line["compared"]


def test_the_second_models_reference_catches_an_altered_token(
        served_root, monkeypatch):
    """The rope model's served tokens against its own forward pass, by the
    shared arithmetic: every eighth decode step handing back the next
    token id reads incorrect."""
    from apex_tpu import serving

    drv = run.load_module(served_root, "drivers", "gpt_rope_serve")
    real = serving.ServingEngine.start

    def broken(self):
        started = self._started
        real(self)
        if not started:
            drv.plant_fault(self, "token_altered", 2048)
        return self

    monkeypatch.setattr(serving.ServingEngine, "start", broken)
    line = _run(served_root, ROPE)
    assert line["correct"] is False
    over = {c["name"] for c in line["compared"] if c["value"] > c["limit"]}
    assert over == {"served_gap_max"}, line["compared"]


def test_a_driver_of_hooks_holds_no_window_code(served_root):
    """The fixture driver and GPT's define the hooks and delegate the rest
    to the one window; no window, study or sweep code lives twice under
    ``perf/``."""
    from perf import serve_window

    for name in ("gpt_serve", "gpt_rope_serve"):
        drv = run.load_module(served_root, "drivers", name)
        for hook in ("build", "weights", "request_flops", "replay",
                     "plant_fault", "FAULTS"):
            assert hasattr(drv, hook), (name, hook)
        for shared in ("window", "check", "release"):
            assert getattr(drv, shared) is getattr(serve_window, shared)
    sources = {}
    for path in glob.glob(os.path.join(PERF, "**", "*.py"), recursive=True):
        with open(path) as f:
            sources[os.path.relpath(path, PERF)] = f.read()
    for pattern in (r"class _Record\b", r'"tpot_p50_ms": _ms\(',
                    r"def _sample\(", r"for rate in rates:",
                    r"eng\.tick\(\)", r"def _position_gaps\("):
        where = [p for p, text in sources.items()
                 if re.search(pattern, text)]
        assert where == ["reference/served.py" if "gaps" in pattern
                         else "serve_window.py"], (pattern, where)
