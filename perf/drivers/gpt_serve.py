"""Driver: GPT serving through the library's seam.

The window drives what ``examples/serving/serve_gpt.py:main`` builds its loop
from: ``ServingEngine(model, variables, ServingConfig(...)).start()``, then
``eng.submit`` for every request that is due and ``eng.tick``, one thread,
as ``main`` does. The model is ``apex_tpu.models.GPTModel`` from the cell's
configuration AS PUBLISHED (learned positions: ``serve_gpt.build_model``
hard-codes rope, a model no published config describes, so the driver does
not go through it), in the precisions ``TransformerConfig``'s defaults give
(float32 parameters, bf16 compute, bf16 cache). The weights come from the
benchmark's seed (``perf/reference/gpt.py``), so that the reference can
follow the same run.

Open loop: the schedule (``perf/loadgen_requests.py``) is fixed by the mix
and the seed before the window opens. Every latency is taken from a
request's DUE time by the host's clock, from outside the engine: a token
counts as served when the ``tick`` that produced it has returned (so the
first token of a request and the token of the decode step that the same tick
runs for it become visible together: that pair is no gap between tokens and
is left out of the gaps). End to end: ``tpot_p50_ms``, the median over all
other gaps between consecutive tokens of the finished requests; the first
token's statistics (due time to first token visible) are per-layer: 52
requests a window do not steady them enough for a bound (PERF.md 2). The
window is ``--seconds`` of
arrivals; once it has closed, the requests still in flight are ticked to
their end (at most ``drain_limit_s``) and their latencies count the wait.
Rates (``mfu_pct.serve``) count the tokens served inside the window alone.
In a traced run the profiler covers the window's last seconds, while
arrivals still come, and is stopped at the close, before the drain.

``check`` replays a sample of the finished requests through the plain
reference; ``study`` reads program, fp8 control and planted faults at the
cell's own size through the cell's limits (``perf/study.py``);
``python3 perf/drivers/gpt_serve.py --sweep ...`` is the rate sweep that the
cell's rate was set from (PERF.md 2).
"""

import os
import sys
import time

import numpy as np

#: the checkout that holds the system under test (this file's own)
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: faults ``plant_fault`` knows, each in the timed path itself
FAULTS = ("stale_position", "block_left_out", "half_prompt",
          "token_altered")


class State:
    pass


def _say(msg):
    print(f"[gpt_serve] {msg}", file=sys.stderr, flush=True)


def _weights(st, seed):
    """The program's variables from the seed, on the device in one jitted
    call, float32 as the engine holds them."""
    import jax

    from perf import gpt_tree
    from perf.reference import gpt as ref

    return jax.jit(lambda key: gpt_tree.to_program(
        ref.init_weights(key, **st.dims)))(ref.seed_key(seed))


def build(cell, config, seed):
    """Model, weights of ``seed``, the engine started (every prefill bucket
    and the decode step lowered and compiled: ``ServingEngine.start``)."""
    from apex_tpu.models import GPTModel
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer import TransformerConfig

    st = State()
    st.cell, st.config = cell, config
    e = cell["engine"]
    if e["max_seq_len"] > config["n_positions"]:
        raise ValueError("the engine's max_seq_len passes the published "
                         "position table")
    st.heads = config["n_head"]
    st.dims = dict(layers=config["n_layer"], hidden=config["n_embd"],
                   vocab=config["assumed"]["padded_vocab_size"],
                   max_positions=config["n_positions"])
    tcfg = TransformerConfig(
        num_layers=config["n_layer"], hidden_size=config["n_embd"],
        num_attention_heads=config["n_head"], vocab_size=st.dims["vocab"],
        max_position_embeddings=config["n_positions"],
        hidden_dropout=0.0, attention_dropout=0.0,
        position_embedding_type="learned",
    )
    st.scfg = ServingConfig(**e)  # the cell's keys are the engine's own
    st.eng = ServingEngine(GPTModel(config=tcfg), _weights(st, seed),
                           st.scfg).start()
    st.weights_of = seed
    return st


def start_run(st, seed, seconds, traffic=None):
    """The weights and the schedule of ``seed`` (the engine's compiled
    programs take the weights as an argument, so one engine serves every
    seed of a study), then one request through every prefill bucket the
    schedule uses and through the decode step: nothing runs for the first
    time inside the window."""
    from perf import loadgen_requests

    st.seed = seed
    if st.weights_of != seed:
        st.eng.variables = _weights(st, seed)
        st.weights_of = seed
    st.schedule = loadgen_requests.request_schedule(
        seed, traffic or st.cell["traffic"], seconds,
        st.config["vocab_size"])
    eng = st.eng
    # the smallest bucket that holds a prompt, by the public config's list
    used = sorted({min(b for b in st.scfg.prefill_buckets
                       if b >= len(r["prompt"])) for r in st.schedule})
    rng = np.random.default_rng([int(seed), 0x7761726D])
    hi = max(len(r["prompt"]) for r in st.schedule)
    for bucket in used:
        n = min(bucket, hi)
        req = eng.submit(rng.integers(0, st.config["vocab_size"], size=n,
                                      dtype=np.int32), max_new_tokens=2)
        while not eng.idle:
            eng.tick()
        if req.state != "completed":
            raise RuntimeError(f"warm-up through bucket {bucket} ended "
                               f"{req.state} ({req.reason})")
    # the engine's watcher counts every compile of the process: what set-up
    # compiled beside the engine (the weights' program) is booked here, so
    # that its count over the window is the window's own
    eng.acknowledge_compiles()


def setup(cell, config, seed, ctx):
    st = build(cell, config, seed)
    start_run(st, seed, ctx.seconds)
    return st


def plant_fault(eng, how, vocab):
    """Break the timed path underneath, for the studies and the tests: the
    engine's own compiled programs, handed tampered arguments or with a
    result altered where it is produced. Returns what undoes it."""
    for seam in ("_decode_c", "_prefill_c"):
        # private names of ``serving/engine.py``: a rename there must fail
        # here, not leave a fault that breaks nothing
        if not hasattr(eng, seam):
            raise AttributeError(f"the engine has no {seam}: the seam the "
                                 "faults are planted at has moved")
    decode, prefills = eng._decode_c, dict(eng._prefill_c)
    nb = eng.config.num_blocks

    def restore():
        eng._decode_c = decode
        eng._prefill_c.update(prefills)

    if how == "stale_position":
        # the decode step reads every lane one position behind
        def stale(pool, variables, tables, positions, *rest):
            return decode(pool, variables, tables,
                          np.maximum(positions - 1, 0), *rest)
        eng._decode_c = stale
    elif how == "block_left_out":
        # the first cache block of every lane never reaches the decode step
        def gapped(pool, variables, tables, *rest):
            tables = np.array(tables)
            tables[:, 0] = nb
            return decode(pool, variables, tables, *rest)
        eng._decode_c = gapped
    elif how == "half_prompt":
        # prefill sees the first half of the prompt, the rest blanked
        def halved(real):
            def prefill(pool, variables, tokens, true_len, *rest):
                tokens = np.array(tokens)
                tokens[int(true_len) // 2:int(true_len)] = 0
                return real(pool, variables, tokens, true_len, *rest)
            return prefill
        for bucket, real in prefills.items():
            eng._prefill_c[bucket] = halved(real)
    elif how == "token_altered":
        # every eighth decode step hands back the next token id
        calls = [0]

        def altered(*args):
            out = decode(*args)
            calls[0] += 1
            if calls[0] % 8:
                return out
            return (out[0], (np.asarray(out[1]) + 1) % vocab) + tuple(out[2:])
        eng._decode_c = altered
    else:
        raise ValueError(f"unknown fault {how!r}")
    return restore


class _Record:
    __slots__ = ("index", "due", "late", "req", "seen", "admit", "times")

    def __init__(self, index, due, late, req):
        self.index, self.due, self.late, self.req = index, due, late, req
        self.seen, self.admit, self.times = 0, None, []


def window(st, seconds, ctx):
    """``seconds`` of arrivals by the schedule, then the drain. The host's
    clock, one thread: submit what is due, tick, look at what the tick
    served."""
    from perf import loadgen_requests, serve_flops

    eng, sched = st.eng, st.schedule
    drain_limit = st.cell["drain_limit_s"]
    clock = time.perf_counter
    records, live, nxt, ticks = [], [], 0, 0
    held, held_peak, held_ticks = 0, 0, 0  # cache tokens the lanes hold
    close = None
    compiles_before = eng.steady_state_compiles
    t0 = clock()
    while True:
        now = clock() - t0
        with ctx.span("submit"):
            while nxt < len(sched) and sched[nxt]["due_s"] <= now:
                r = sched[nxt]
                req = eng.submit(r["prompt"], r["max_new_tokens"])
                rec = _Record(nxt, r["due_s"], now - r["due_s"], req)
                records.append(rec)
                if not req.terminal:
                    live.append(rec)
                nxt += 1
        if close is None and now >= seconds:
            close = now
            ctx.stop_trace()  # before the drain; seconds, off the window
        if close is not None and (not live or now > close + drain_limit):
            break
        if not live:
            # nothing in flight: wait for the next arrival (or the close)
            # instead of burning empty scheduler ticks, as main() does
            with ctx.span("wait"):
                time.sleep(0.0005)
            ctx.poll()
            continue
        began = clock() - t0
        with ctx.span("tick"):
            eng.tick()
        ended = clock() - t0
        ticks += 1
        with ctx.span("observe"):
            still = []
            for rec in live:
                k = len(rec.req.tokens_out)
                if k > rec.seen:
                    if rec.admit is None:
                        rec.admit = began
                    rec.times.extend([ended] * (k - rec.seen))
                    rec.seen = k
                if not rec.req.terminal:
                    still.append(rec)
            live = still
            # what the pool HOLDS, not what it reserves: a lane's prompt
            # and every token served but the last, whose keys the next
            # decode step writes
            now_held = sum(len(rec.req.prompt) + rec.seen - 1
                           for rec in live if rec.seen)
            held_peak = max(held_peak, now_held)
            if close is None:
                held, held_ticks = held + now_held, held_ticks + 1
        ctx.poll()
    drained = clock() - t0 - close

    d = st.dims
    done = [r for r in records if r.req.state == "completed"]
    shed = [r for r in records if r.req.state == "rejected"]
    never = [r for r in records if r.req.state not in ("completed",
                                                      "rejected")]
    # a request's first token and the token of the decode step the same
    # tick runs become visible together: that pair is no gap a user sees,
    # and it is left out (one a request)
    gaps = [b - a for r in done for a, b in zip(r.times, r.times[1:])
            if b > a]
    ttft = [r.times[0] - r.due for r in records if r.times]
    queue = [r.admit - r.due for r in records if r.admit is not None]
    late = [r.late for r in records]
    flops_in, tokens_in, prompts_in = 0.0, 0, 0
    for r in records:
        k = sum(t <= close for t in r.times)
        if k:
            flops_in += serve_flops.gpt_request_flops(
                d["layers"], d["hidden"], d["vocab"], len(r.req.prompt), k)
            tokens_in += k
            prompts_in += len(r.req.prompt)
    pct = loadgen_requests.percentile
    stats = eng.stats()
    st.finished = [{"index": r.index, "prompt": np.asarray(r.req.prompt),
                    "served": list(r.req.tokens_out)} for r in done]
    st.never_answered = len(never)
    st.steady_compiles = int(stats["steady_state_compiles"]
                             - compiles_before)
    quarter = max(1, len(queue) // 4)
    st.counters = {
        "requests": len(records), "completed": len(done),
        "shed": len(shed), "never_answered": len(never), "ticks": ticks,
        "drain_s": drained, "in_flight_at_close": sum(
            1 for r in records if not r.times or r.times[-1] > close),
        "model_flops": flops_in, "tokens_out_in_window": tokens_in,
        "prompt_tokens_in_window": prompts_in,
        "queue_wait_p95_ms": _ms(pct(queue, 95)),
        "queue_wait_first_quarter_ms": _ms(pct(queue[:quarter], 50)),
        "queue_wait_last_quarter_ms": _ms(pct(queue[-quarter:], 50)),
        "ttft_mean_ms": _ms(float(np.mean(ttft)) if ttft else None),
        "ttft_p50_ms": _ms(pct(ttft, 50)),
        "ttft_p95_ms": _ms(pct(ttft, 95)),
        "tpot_p95_ms": _ms(pct(gaps, 95)),
        "loadgen_late_p95_ms": _ms(pct(late, 95)),
        "kv_pool_peak_blocks": int(stats["kv_pool_peak_blocks"]),
        "kv_pool_blocks": int(st.scfg.num_blocks),
        "kv_held_peak_tokens": held_peak,
        "kv_held_mean_tokens": held / max(held_ticks, 1),
        "kv_pool_tokens": int(st.scfg.num_blocks * st.scfg.block_size),
    }
    e2e = {"tpot_p50_ms": _ms(pct(gaps, 50))}
    _say("window {:.2f} s + drain {:.2f} s: {} requests, {} completed, {} "
         "shed, {} never answered, {} ticks; tpot p50 {} p95 {} ms over {} "
         "gaps, ttft mean {} p50 {} p95 {} ms, queue wait p95 {} ms, "
         "generator late p95 {} ms, pool peak {} of {} blocks reserved, {} "
         "(mean {:.0f}) of {} tokens held".format(
             close, drained, len(records), len(done), len(shed),
             len(never), ticks, *(_fmt(x) for x in (
                 e2e["tpot_p50_ms"], st.counters["tpot_p95_ms"])),
             len(gaps), *(_fmt(x) for x in (
                 st.counters["ttft_mean_ms"],
                 st.counters["ttft_p50_ms"], st.counters["ttft_p95_ms"],
                 st.counters["queue_wait_p95_ms"],
                 st.counters["loadgen_late_p95_ms"])),
             st.counters["kv_pool_peak_blocks"], st.scfg.num_blocks,
             held_peak, st.counters["kv_held_mean_tokens"],
             st.counters["kv_pool_tokens"]))
    if not gaps or not ttft:
        raise RuntimeError("the window served no token: nothing to report")
    return {"attempted": len(records), "failed": len(shed) + len(never),
            "window_s": close, "end_to_end": e2e, "counters": st.counters}


def _ms(seconds):
    return None if seconds is None else 1e3 * seconds


def _fmt(x):
    return "-" if x is None else f"{x:.2f}"


def release(st):
    st.eng = None


def replay(st, seed, requests, candidate="served"):
    """The reference over each request's prompt and served tokens: per
    request the per-position gaps and whether the token is the reference's
    best (``reference/gpt_serving.py``). ``candidate="fp8"`` reads the
    control's tokens in the served tokens' stead."""
    from perf.reference import gpt as ref
    from perf.reference import gpt_serving

    w = ref.init_weights(ref.seed_key(seed), **st.dims)
    out = [gpt_serving.served_gaps(
        w, r["prompt"], r["served"], heads=st.heads,
        seq=st.cell["engine"]["max_seq_len"],
        rows=st.cell["traffic"]["answer"]["max"], candidate=candidate)
        for r in requests]
    return [g for g, _ in out], [s for _, s in out]


def _sample(st, seed):
    from perf import loadgen_requests

    return loadgen_requests.sample_finished(
        seed, st.finished, st.cell["check_requests"])


def _extra(st):
    return [("requests_never_answered", st.never_answered, 0),
            ("steady_state_compiles", st.steady_compiles, 0)]


def check(st, ctx):
    """A sample of the window's finished requests, the longest among them,
    against the float32 reference (PERF.md 2). In a traced run also the
    trace's device time by program, for the readers, from the events the
    harness loaded once (``ctx.events``): it drops them before it calls the
    readers."""
    from perf import compare_serving

    if ctx.events is not None:
        from perf import serve_trace

        before = time.perf_counter()
        st.counters["programs"] = serve_trace.by_program(ctx.events,
                                                         chips=ctx.chips)
        ctx.trace_reads["by_program"] = time.perf_counter() - before
    sample = _sample(st, st.seed)
    _say(f"replaying {len(sample)} of {len(st.finished)} finished requests "
         f"({sum(len(r['served']) for r in sample)} served tokens)")
    gaps, same = replay(st, st.seed, sample)
    return compare_serving.serving(gaps, same, st.cell["limits"], _extra(st))


def study(cell, config, seeds, ctx, controls=3):
    """Readings for the limits, and the proof that the comparison fails what
    it has to, at the cell's own size and load: on every seed a window of
    ``study_seconds`` through the engine and its sample against the
    reference; on the first ``controls`` seeds also the fp8 control (the
    token the reference in fp8 puts first, at every position of the same
    sample) and a window with each planted fault the cell's file lists under
    ``study_faults`` (all of ``FAULTS`` if it lists none). One process, one
    engine.
    Yields (kind, seed, compared, readings)."""
    from perf import compare_serving

    seconds = cell["study_seconds"]
    st = build(cell, config, seeds[0])

    def one(seed, kind, candidate="served"):
        gaps, same = replay(st, seed, _sample(st, seed), candidate)
        extra = _extra(st) if candidate == "served" else []
        return (kind, seed,
                compare_serving.serving(gaps, same, cell["limits"], extra),
                compare_serving.serving(gaps, same, None, extra))

    for n, seed in enumerate(seeds):
        start_run(st, seed, seconds)
        window(st, seconds, ctx)
        yield one(seed, "program")
        if n >= controls:
            continue
        yield one(seed, "control_fp8", candidate="fp8")
        for how in cell.get("study_faults", FAULTS):
            # the control's replay compiled a program of its own: booked
            # here, or the engine's watcher counts it into the next window
            st.eng.acknowledge_compiles()
            undo = plant_fault(st.eng, how, st.dims["vocab"])
            try:
                window(st, seconds, ctx)
            finally:
                undo()
            yield one(seed, f"fault_{how}")


def sweep(cell, config, seed, rates, seconds, ctx):
    """The rate sweep the cell's rate is set from: one engine, one window
    of ``seconds`` and its drain at each rate, the mix otherwise the
    cell's. Prints one JSON line a rate: the window's counters and
    end-to-end metrics."""
    import json

    st = build(cell, config, seed)
    for rate in rates:
        start_run(st, seed, seconds, traffic=dict(cell["traffic"],
                                                  rate_rps=rate))
        result = window(st, seconds, ctx)
        print(json.dumps(dict(
            st.counters, rate_rps=rate, **result["end_to_end"],
            tokens_out_per_s=st.counters["tokens_out_in_window"]
            / result["window_s"])), flush=True)


def main(argv=None):
    import argparse

    sys.path.insert(0, os.path.join(REPO, "perf"))
    import run

    p = argparse.ArgumentParser(description="the rate sweep")
    p.add_argument("--workload", required=True)
    p.add_argument("--sweep", required=True, help="rates, comma-separated")
    p.add_argument("--seed", type=int, default=2147483659)
    p.add_argument("--seconds", type=float, default=50.0)
    args = p.parse_args(argv)
    bench = run._load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cell = run._load_json(os.path.join(
        REPO, "perf", "workloads", args.workload + ".json"))
    config = run._load_json(os.path.join(REPO, cfg["file"]))
    device = run._device_entry(int(entry["chips"]), allow_cpu=False)
    run._enable_compile_cache(REPO)
    ctx = run.Context(REPO, cell, config, args.seed, args.seconds, 0, device,
                      None)
    sweep(cell, config, args.seed,
          [float(r) for r in args.sweep.split(",")], args.seconds, ctx)
    return 0


if __name__ == "__main__":
    sys.exit(main())
