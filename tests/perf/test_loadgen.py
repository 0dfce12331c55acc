"""perf/loadgen.py: a pure function of the seed."""

import numpy as np
import pytest

from _bench import load

loadgen = load("loadgen.py")


@pytest.mark.parametrize("seed", [0, 4, 2**31 + 11, 4_000_000_007])
def test_token_corpus_rows_differ_and_repeat_by_seed(seed):
    a = loadgen.token_corpus(seed, 50257, 24, 128)
    assert a.shape == (24, 129) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 50257
    assert len({row.tobytes() for row in a}) == 24
    assert np.array_equal(a, loadgen.token_corpus(seed, 50257, 24, 128))
    assert not np.array_equal(a, loadgen.token_corpus(seed + 1, 50257, 24,
                                                      128))


def test_token_corpus_has_structure_to_learn():
    a = loadgen.token_corpus(4, 50257, 24, 128)
    step = np.diff(a.reshape(-1).astype(np.int64)) % 50257
    assert step.min() >= 1 and step.max() <= 4
