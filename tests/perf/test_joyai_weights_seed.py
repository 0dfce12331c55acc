"""The JoyAI cell's ``weights_seed``: the initial weights are one draw for
every ``--seed`` (which still draws the corpus), so that every seed gives a
step the same work; a run made so reads correct, because the reference
follows the same weights. Without the key the weights come from the seed.

Nothing here is a device number: the result line says ``cpu``.
"""

import io

import numpy as np
import pytest

from _bench import fixture_root, load
from test_run_tiny_joyai import JOYAI_TINY, PRETRAIN_TINY, TRAIN_METRICS

run = load("run.py", name="perf_test_run_joyai_weights_seed")
FIXED = dict(PRETRAIN_TINY, weights_seed=2**31 + 11)
SEEDS = (2**31 + 7, 2**31 + 29)


@pytest.fixture
def root(tmp_path):
    return fixture_root(tmp_path, {"joyai_tiny.pretrain": FIXED},
                        {"joyai_tiny": JOYAI_TINY}, TRAIN_METRICS)


def _state(root, cell):
    drv = run.load_module(root, "drivers", "joyai_pretrain")
    from perf.reference import joyai_llm_flash as ref

    st = drv.State()
    st.cell, st.config = cell, JOYAI_TINY
    st.dims = ref.dims_of(JOYAI_TINY)
    return drv, st


def _leaves(w):
    return {k: np.asarray(v) for k, v in w.items()}


@pytest.mark.parametrize("cell, same", [(FIXED, True),
                                        (PRETRAIN_TINY, False)])
def test_the_weights_follow_weights_seed_and_the_corpus_the_seed(
        root, cell, same):
    drv, st = _state(root, cell)
    a, b = (_leaves(drv._make_w0(st, s)) for s in SEEDS)
    assert a.keys() == b.keys()
    assert all(np.array_equal(a[k], b[k]) for k in a) is same
    if same:
        want = _leaves(drv._make_w0(_state(root, PRETRAIN_TINY)[1],
                                    FIXED["weights_seed"]))
        assert all(np.array_equal(a[k], want[k]) for k in a)
    rows = [drv._corpus(st, s) for s in SEEDS]
    assert not np.array_equal(*rows)


def test_a_run_on_the_fixed_weights_is_correct(root):
    out = io.StringIO()
    line = run.run_cell(root, "joyai_tiny.pretrain", SEEDS[1], 0.3, 0,
                        allow_cpu=True, out=out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
