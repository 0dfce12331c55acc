"""One serving window for every served model, and what follows it: the
check against the plain reference, the study of the check's limits and the
rate sweep.

The window drives what ``examples/serving/serve_gpt.py:main`` builds its
loop from: a started ``ServingEngine``, then ``eng.submit`` for every
request that is due and ``eng.tick``, one thread, as ``main`` does.

Open loop: the schedule (``perf/loadgen_requests.py``) is fixed by the mix
and the seed before the window opens. Every latency is taken from a
request's DUE time by the host's clock, from outside the engine: a token
counts as served when the ``tick`` that produced it has returned (so the
first token of a request and the token of the decode step that the same tick
runs for it become visible together: that pair is no gap between tokens and
is left out of the gaps). End to end: ``tpot_p50_ms``, the median over all
other gaps between consecutive tokens of the finished requests; the first
token's statistics (due time to first token visible) are per-layer (PERF.md
2). The window is ``--seconds`` of arrivals; once it has closed, the
requests still in flight are ticked to their end (at most the cell's
``drain_limit_s``) and their latencies count the wait. Rates
(``mfu_pct.serve``) count the tokens served inside the window alone. In a
traced run the profiler covers the window's last seconds, while arrivals
still come, and is stopped at the close, before the drain.

What is a model's own comes from its driver module (``perf/drivers/
<driver>.py``), which defines these hooks and delegates the rest here:

- ``build(cell, config, seed)``: the model, its weights of ``seed`` and the
  engine started; returns a state with ``eng`` (the engine), ``scfg`` (its
  ``ServingConfig``) and ``vocab`` (the rows of the output head, which a
  planted fault's altered token wraps round);
- ``weights(st, seed)``: the engine's variables of ``seed``, on the device;
- ``request_flops(st, prompt_len, tokens_served)``: the operations one
  request needs for its prompt and that many served tokens (``mfu_pct.
  serve``'s numerator; the model's count, kept with the benchmark);
- ``replay(st, seed, requests, candidate)``: the model's plain reference
  over each request's prompt and served tokens (``reference/served.py``),
  per request the gaps and whether each token is the reference's best;
  ``candidate="fp8"`` reads the control's tokens in the served ones' stead;
- ``plant_fault(eng, how, vocab)`` and ``FAULTS``: the timed path broken
  underneath, for the studies and the tests; returns what undoes it;
- optionally ``counters(st, stats)``: counters a model adds to the window's
  (such as a recurrent state's bytes), from the engine's ``stats()``.
"""

import os
import sys
import time

import numpy as np

#: the checkout that holds the system under test (this file's own)
REPO = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))


def _say(msg):
    print(f"[serve] {msg}", file=sys.stderr, flush=True)


def build(drv, cell, config, seed):
    """The driver's model and engine, with what the window asks of every
    state."""
    st = drv.build(cell, config, seed)
    st.drv, st.cell, st.config, st.weights_of = drv, cell, config, seed
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
        st.eng.variables = st.drv.weights(st, seed)
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


def setup(drv, cell, config, seed, ctx):
    st = build(drv, cell, config, seed)
    start_run(st, seed, ctx.seconds)
    return st


class _Record:
    __slots__ = ("index", "due", "late", "req", "seen", "admit", "times")

    def __init__(self, index, due, late, req):
        self.index, self.due, self.late, self.req = index, due, late, req
        self.seen, self.admit, self.times = 0, None, []


def window(st, seconds, ctx):
    """``seconds`` of arrivals by the schedule, then the drain. The host's
    clock, one thread: submit what is due, tick, look at what the tick
    served."""
    from perf import loadgen_requests

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
            flops_in += st.drv.request_flops(st, len(r.req.prompt), k)
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
    if hasattr(st.drv, "counters"):
        st.counters.update(st.drv.counters(st, stats))
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


def hlo_text(st):
    """``{program: text}``: the compiled decode step's text under the name
    the trace gives its runs (the cell's ``programs.decode``), so that a
    traced run books the decode program's ops by its own instructions
    (``trace_reduce.reduce``'s ``program_scopes``). The engine hands out no
    compiled program: this reads the one it holds, ``_decode_c`` (the seam
    the planted faults use too). The prefill buckets are programs of one
    name that the trace cannot tell apart, so none is handed out. Asked for
    before ``release`` drops the engine."""
    from apex_tpu.analysis.hlo.parser import module_text

    return {st.cell["programs"]["decode"]: module_text(st.eng._decode_c)}


def scope_names():
    """The names ``hlo_scopes.scope_map`` reads a serving program's
    ``op_name`` paths by. The engine opens no phase of its own: a path
    starts with the jitted function, so that is the phase (``jit(decode)``,
    ``jit(prefill)``); the model's scopes and the kernels' key are the
    program's registries, as a training step's."""
    from apex_tpu.monitor.goodput import scopes

    return {"phases": ("jit(decode)", "jit(prefill)"),
            "scopes": scopes.MODEL_SCOPES, "kernel_key": scopes.KERNEL_KEY}


def release(st):
    st.eng = None


def _sample(st, seed):
    from perf import loadgen_requests

    return loadgen_requests.sample_finished(
        seed, st.finished, st.cell["check_requests"])


def _extra(st):
    return [("requests_never_answered", st.never_answered, 0),
            ("steady_state_compiles", st.steady_compiles, 0)]


def check(st, ctx):
    """A sample of the window's finished requests, the longest among them,
    against the model's float32 reference (PERF.md 2). In a traced run also
    the trace's device time by program, for the readers, from the events
    the harness loaded once (``ctx.events``): it drops them before it calls
    the readers."""
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
    gaps, same = st.drv.replay(st, st.seed, sample, "served")
    return compare_serving.serving(gaps, same, st.cell["limits"], _extra(st))


def study(drv, cell, config, seeds, ctx, controls=3):
    """Readings for the limits, and the proof that the comparison fails what
    it has to, at the cell's own size and load: on every seed a window of
    ``study_seconds`` through the engine and its sample against the
    reference; on the first ``controls`` seeds also the fp8 control (the
    token the reference in fp8 puts first, at every position of the same
    sample) and a window with each planted fault the cell's file lists under
    ``study_faults`` (all of the driver's ``FAULTS`` if it lists none). One
    process, one engine.
    Yields (kind, seed, compared, readings)."""
    from perf import compare_serving

    seconds = cell["study_seconds"]
    st = build(drv, cell, config, seeds[0])

    def one(seed, kind, candidate="served"):
        gaps, same = drv.replay(st, seed, _sample(st, seed), candidate)
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
        for how in cell.get("study_faults", drv.FAULTS):
            # the control's replay compiled a program of its own: booked
            # here, or the engine's watcher counts it into the next window
            st.eng.acknowledge_compiles()
            undo = drv.plant_fault(st.eng, how, st.vocab)
            try:
                window(st, seconds, ctx)
            finally:
                undo()
            yield one(seed, f"fault_{how}")


def sweep(drv, cell, config, seed, rates, seconds, ctx):
    """The rate sweep a cell's rate is set from: one engine, one window of
    ``seconds`` and its drain at each rate, the mix otherwise the cell's.
    Prints one JSON line a rate: the window's counters and end-to-end
    metrics."""
    import json

    st = build(drv, cell, config, seed)
    for rate in rates:
        start_run(st, seed, seconds, traffic=dict(cell["traffic"],
                                                  rate_rps=rate))
        result = window(st, seconds, ctx)
        print(json.dumps(dict(
            st.counters, rate_rps=rate, **result["end_to_end"],
            tokens_out_per_s=st.counters["tokens_out_in_window"]
            / result["window_s"])), flush=True)


def main(drv, argv=None):
    """``python3 perf/drivers/<driver>.py --workload <cell> --sweep
    <rates>``: the rate sweep, on the chip."""
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
    sweep(drv, cell, config, args.seed,
          [float(r) for r in args.sweep.split(",")], args.seconds, ctx)
    return 0
