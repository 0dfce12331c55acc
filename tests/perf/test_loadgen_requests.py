"""perf/loadgen_requests.py: the open-loop request generator is a pure
function of the seed and the mix, and gives every seed the same work."""

import json
import os

import numpy as np
import pytest

from _bench import PERF, load

lg = load("loadgen_requests.py")
SEEDS = [0, 4, 2**31 + 11, 4_000_000_007]


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(PERF, "workloads", "gpt2_345m.chat.json")) as f:
        return json.load(f)["traffic"]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_schedule(traffic, seed):
    a = lg.request_schedule(seed, traffic, 50.0, 50257)
    b = lg.request_schedule(seed, traffic, 50.0, 50257)
    c = lg.request_schedule(seed + 1, traffic, 50.0, 50257)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    assert [r["max_new_tokens"] for r in a] == [
        r["max_new_tokens"] for r in b]
    assert [r["due_s"] for r in a] != [r["due_s"] for r in c]


@pytest.mark.parametrize("seed", SEEDS)
def test_lengths_stay_inside_their_clips_and_fit_the_engine(traffic, seed):
    sched = lg.request_schedule(seed, traffic, 50.0, 50257)
    p, a = traffic["prompt"], traffic["answer"]
    for r in sched:
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert a["min"] <= r["max_new_tokens"] <= a["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= 1024
        assert r["prompt"].dtype == np.int32
        assert 0 <= r["prompt"].min() and r["prompt"].max() < 50257
    answers = sorted(r["max_new_tokens"] for r in sched)
    # the answers' median is the mix's, and a quarter of them end at the
    # limit on new tokens
    assert abs(answers[len(answers) // 2] - a["median"]) <= 6
    assert 0.2 <= np.mean(np.array(answers) == a["max"]) <= 0.3


def test_prompts_are_cut_to_the_positions_their_answers_leave(traffic):
    """The prompts' log-normal has the source's median, over the model's
    1024 positions with any answer: a prompt keeps what ``context_max`` less
    its answer leaves it, so most requests fill their positions exactly and
    the lanes hold what they reserve."""
    n = round(traffic["rate_rps"] * 50.0)
    raw = lg.lognormal_lengths(n, **{k: traffic["prompt"][k] for k in (
        "median", "sigma")}, lo=1, hi=10**6)
    assert abs(np.median(raw) - 1020) <= 25
    prompts, answers = lg.length_pairs(n, traffic)
    assert np.all(prompts + answers <= traffic["context_max"])
    assert np.mean(prompts + answers == traffic["context_max"]) > 0.5
    assert np.mean(prompts + answers) > 0.85 * traffic["context_max"]
    # without the key nothing is cut
    loose = dict(traffic)
    del loose["context_max"]
    p2, a2 = lg.length_pairs(n, loose)
    assert np.array_equal(a2, answers) and p2.sum() > prompts.sum()
    with pytest.raises(ValueError, match="under its minimum"):
        lg.length_pairs(n, dict(traffic, context_max=260))


def test_the_pairing_is_the_mixs_own_and_not_the_seeds(traffic):
    scheds = [lg.request_schedule(s, traffic, 50.0, 50257) for s in SEEDS]
    pairs = [sorted((len(r["prompt"]), r["max_new_tokens"]) for r in s)
             for s in scheds]
    assert all(p == pairs[0] for p in pairs)
    n = len(scheds[0])
    want = sorted(zip(*(x.tolist() for x in lg.length_pairs(n, traffic))))
    assert pairs[0] == want


def test_every_seed_offers_the_same_work_in_another_order(traffic):
    scheds = [lg.request_schedule(s, traffic, 50.0, 50257) for s in SEEDS]
    n = round(traffic["rate_rps"] * 50.0)
    assert {len(s) for s in scheds} == {n}
    assert len({lg.offered_tokens(s) for s in scheds}) == 1
    for s in scheds:
        assert sorted(len(r["prompt"]) for r in s) == sorted(
            len(r["prompt"]) for r in scheds[0])
        # the mix's own gaps, all but the one that would end the window
        gaps = np.diff([r["due_s"] for r in s])
        full = lg.exponential_gaps(n, 50.0)
        assert np.allclose(sorted(np.append(gaps, full.sum() - gaps.sum())),
                           sorted(full))
    orders = {tuple(len(r["prompt"]) for r in s) for s in scheds}
    assert len(orders) == len(SEEDS)


@pytest.mark.parametrize("seconds", [10.0, 50.0])
def test_due_times_are_the_schedules_own(traffic, seconds):
    """Due times come from the mix and the seed alone: ascending, the first
    at 0, the last inside the window, the mean gap 1 / rate; nothing that
    happens to a request can move them (the function sees no engine)."""
    sched = lg.request_schedule(7, traffic, seconds, 50257)
    due = np.array([r["due_s"] for r in sched])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert due[-1] < seconds
    assert len(sched) == round(traffic["rate_rps"] * seconds)
    gaps = lg.exponential_gaps(len(sched), seconds)
    assert gaps.sum() == pytest.approx(seconds)
    # an exponential's gaps: the median is ln 2 of the mean
    assert np.median(gaps) / gaps.mean() == pytest.approx(np.log(2), rel=0.05)


def test_unknown_arrivals_and_empty_windows_are_errors(traffic):
    with pytest.raises(ValueError, match="arrivals"):
        lg.request_schedule(1, dict(traffic, arrivals="bursty"), 50.0, 100)
    with pytest.raises(ValueError, match="no request"):
        lg.request_schedule(1, traffic, 0.1, 100)


def test_percentile_of_nothing_is_nothing():
    assert lg.percentile([], 95) is None
    assert lg.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    assert lg.percentile(list(range(101)), 95) == 95.0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_checked_sample_is_drawn_from_the_seed_with_the_longest(seed):
    rng = np.random.default_rng(3)
    finished = [{"index": i, "prompt": np.zeros(int(rng.integers(16, 700))),
                 "served": [0] * int(rng.integers(16, 256))}
                for i in range(70)]
    a = lg.sample_finished(seed, finished, 12)
    assert len(a) == 12 and len({r["index"] for r in a}) == 12
    assert a == lg.sample_finished(seed, finished, 12)
    longest = max(finished,
                  key=lambda r: len(r["prompt"]) + len(r["served"]))
    assert longest["index"] in {r["index"] for r in a}
    assert lg.sample_finished(seed, finished[:5], 12) == finished[:5]
    other = lg.sample_finished(seed + 1, finished, 12)
    assert {r["index"] for r in other} != {r["index"] for r in a}
