"""perf/compare.py: the arithmetic of ``correct``, on hand-worked numbers."""

import numpy as np
import pytest

from _bench import load

compare = load("compare.py")


def _norms(**kw):
    return {k: np.asarray(v, np.float64) for k, v in kw.items()}


def test_worst_leaf_gap_is_of_the_norms_against_leaf_or_median():
    want = _norms(a=[4.0, 2.0], b=1.0, tiny=1e-6)
    got = _norms(a=[4.0, 2.2], b=1.0, tiny=3e-6)
    # median of (4, 2, 1, 1e-6) is 1.5: a[1] is off by 0.2/2 = 0.1; the
    # all-but-zero leaf is off by 2e-6 against the median 1.5, not its own
    # norm
    gap, where = compare.worst_leaf_gap(got, want)
    assert gap == pytest.approx(0.1) and where == "a[1]"
    gap, where = compare.worst_leaf_gap(got, want, keep=np.array(
        [True, False, True, True]))
    assert gap == pytest.approx(2e-6 / 1.5) and where == "tiny"


def test_a_leaf_that_did_not_move_reads_one():
    want = _norms(a=2.0, b=2.0, c=2.0)
    got = _norms(a=2.0, b=0.0, c=4.0)  # unmoved; moved double
    gap, where = compare.worst_leaf_gap(got, want)
    assert gap == pytest.approx(1.0) and where == "b"


def test_leaves_must_be_the_references():
    with pytest.raises(ValueError, match="not the reference's"):
        compare.worst_leaf_gap(_norms(a=1.0), _norms(b=1.0))


def _run(scale=1.0, skipped=0.0):
    return {"losses": [10.0 * scale, 9.0 * scale, 8.0],
            "g1": _norms(w=[1.0 * scale, 2.0], k_bias=1e-9),
            "delta": _norms(w=[3.0, 3.0 * scale], k_bias=3e-4 * scale),
            "skipped": skipped}


def test_training_compares_only_what_has_a_limit():
    limits = {"loss_gap_step2": 0.05, "grad_norm_gap": 0.05,
              "update_norm_gap": 0.05}
    same = compare.training(_run(), _run(), limits)
    assert [c["name"] for c in same] == [
        "loss_gap_step2", "grad_norm_gap", "update_norm_gap",
        "skipped_updates"]
    assert all(c["value"] == 0 for c in same)
    off = {c["name"]: c for c in compare.training(_run(1.1), _run(), limits)}
    assert off["loss_gap_step2"]["value"] == pytest.approx(0.1)
    assert off["grad_norm_gap"]["value"] == pytest.approx(0.1 / 1.0)
    assert off["grad_norm_gap"]["where"] == "w[0]"
    # k_bias moved by 10% too, but its reference gradient is nought to
    # rounding (1e-9 against a median of 1): it is left out of the change
    assert off["update_norm_gap"]["where"] == "w[1]"
    assert off["update_norm_gap"]["value"] == pytest.approx(0.1)
    assert all(off[n]["value"] > off[n]["limit"] for n in limits)
    every = compare.training(_run(1.1), _run(), None)
    assert [c["name"] for c in every][:3] == [
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3"]
    assert all(c["value"] <= c["limit"] for c in every)  # readings only


def test_a_skipped_update_or_a_nan_is_never_correct():
    limits = {"grad_norm_gap": 1.0, "update_norm_gap": 1.0}
    out = compare.training(_run(skipped=1.0), _run(), limits)
    assert out[-1] == {"name": "skipped_updates", "value": 1.0, "limit": 0.0}
    bad = _run()
    bad["g1"]["w"] = np.array([np.nan, 2.0])
    out = {c["name"]: c for c in compare.training(bad, _run(), limits)}
    assert out["grad_norm_gap"]["value"] == 1e30


def test_correct_is_every_number_at_or_under_its_limit():
    ok = [{"name": "a", "value": 0.5, "limit": 0.5},
          {"name": "b", "value": 0.0, "limit": 0.0}]
    assert compare.correct(ok) is True
    assert compare.correct(ok + [{"name": "c", "value": 1e-9,
                                  "limit": 0.0}]) is False
