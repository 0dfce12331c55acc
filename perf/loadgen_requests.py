"""Serving traffic from the seed: one general open-loop request generator,
parameters in the cell's file (its ``traffic`` group).

A pure function of its arguments; nothing here imports the program. The
schedule is fixed by the mix and the seed before the window opens and never
by completions (an open loop): every request has a DUE time, latencies are
taken from it, and how late the generator ran is reported beside them.

Every seed gets the same work in another order. A mix at ``rate_rps`` over
``seconds`` is ``round(rate_rps * seconds)`` requests whose inter-arrival
gaps are the exponential distribution's quantiles (a Poisson process's gaps,
stratified: one gap from each of n equal slices of probability) and whose
prompt and answer lengths are the quantiles of their clipped log-normals.
Which answer goes with which prompt is drawn from the mix alone (its number
of requests), never from the seed, and where the mix states ``context_max``
(the positions the model has) a prompt keeps its last ``context_max -
answer`` tokens, as a client does that cuts a conversation's history to
what fits: every seed therefore holds the same (prompt, answer) pairs. The
seed permutes the gaps and the pairs independently and draws the token ids.
So two seeds differ in who arrives when and beside whom, not in how many
tokens the window holds: a run-to-run spread then measures the system, not
the draw (with lengths and a Poisson count drawn anew per seed, the offered
work alone swings by a tenth).
"""

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n, median, sigma, lo, hi):
    """The n stratified quantiles of a log-normal (``median``, ``sigma`` of
    the logarithm), rounded to whole tokens and clipped to [lo, hi]."""
    z = np.array([_NORMAL.inv_cdf(q) for q in _quantiles(n)])
    lengths = np.rint(median * np.exp(sigma * z)).astype(np.int64)
    return np.clip(lengths, lo, hi)


def exponential_gaps(n, span_s):
    """The n stratified quantiles of an exponential distribution, scaled so
    that they add up to ``span_s``: arrivals that start at 0 and whose n-th
    gap ends with the span."""
    gaps = -np.log1p(-_quantiles(n))
    return gaps * (span_s / gaps.sum())


def length_pairs(n, traffic):
    """The mix's n (prompt, answer) lengths, the same for every seed: the
    quantiles of the two log-normals, paired by a permutation drawn from n
    alone, the prompt then cut to ``context_max`` less its answer where the
    mix states one."""
    p, a = traffic["prompt"], traffic["answer"]
    prompts = lognormal_lengths(n, p["median"], p["sigma"], p["min"],
                                p["max"])
    answers = lognormal_lengths(n, a["median"], a["sigma"], a["min"],
                                a["max"])
    answers = answers[np.random.default_rng([n, 0x70616972]).permutation(n)]
    if traffic.get("context_max") is not None:
        prompts = np.minimum(prompts, int(traffic["context_max"]) - answers)
        if prompts.min() < p["min"]:
            raise ValueError("context_max leaves a prompt under its minimum")
    return prompts, answers


def request_schedule(seed, traffic, seconds, vocab):
    """The requests due in ``seconds``: a list of dicts ``due_s`` (seconds
    from the window's opening, ascending, the first at 0, all under
    ``seconds``), ``prompt`` (int32 token ids from ``[0, vocab)``) and
    ``max_new_tokens``. ``traffic``: ``rate_rps``, ``prompt`` and ``answer``
    (each ``median``, ``sigma``, ``min``, ``max``), optionally
    ``context_max`` (prompt + answer may not pass it: the prompt is cut),
    and ``arrivals``, which has to say ``poisson_stratified``, the one
    process there is."""
    if traffic.get("arrivals") != "poisson_stratified":
        raise ValueError(f"unknown arrivals {traffic.get('arrivals')!r}")
    n = int(round(traffic["rate_rps"] * seconds))
    if n < 1:
        raise ValueError("the mix gives no request in the window")
    rng = np.random.default_rng([int(seed), 0x72657173])
    gaps = exponential_gaps(n, float(seconds))[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    prompts, answers = length_pairs(n, traffic)
    order = rng.permutation(n)
    prompts, answers = prompts[order], answers[order]
    return [{"due_s": float(due[i]),
             "prompt": rng.integers(0, vocab, size=int(prompts[i]),
                                    dtype=np.int32),
             "max_new_tokens": int(answers[i])} for i in range(n)]


def percentile(values, q):
    """The q-th percentile (linear between the two nearest ranks), or None
    of nothing: nothing to read is not 0."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def sample_finished(seed, finished, count):
    """Which of the ``finished`` requests (dicts with ``index`` and
    ``served``, the tokens it was served) the output check replays:
    ``count`` of them drawn from the seed, the longest (prompt + served)
    among them; all of them if there are no more."""
    if len(finished) <= count:
        return list(finished)
    rng = np.random.default_rng([int(seed), 0x636865636B])
    longest = max(range(len(finished)), key=lambda i: (
        len(finished[i]["prompt"]) + len(finished[i]["served"]),
        -finished[i]["index"]))
    others = [i for i in range(len(finished)) if i != longest]
    picked = [longest] + list(rng.choice(others, size=count - 1,
                                         replace=False))
    return [finished[i] for i in sorted(picked)]


def offered_tokens(schedule):
    """(prompt tokens, answer tokens) a schedule offers: the same for every
    seed of one mix and length."""
    return (sum(len(r["prompt"]) for r in schedule),
            sum(r["max_new_tokens"] for r in schedule))
