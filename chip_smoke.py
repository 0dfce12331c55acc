#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of GPT-2 345M (24 x 1024 x 16 heads, seq 1024, vocab 50304,
random weights from a seed):

  device   the accelerator JAX found, and that the program will use it
           (no phase below runs unless this one passes)
  clock    is ``block_until_ready`` honest? a 4096^3 bf16 matmul chain timed
           by the host clock must come out at or below the chip's peak
  trainer  ``examples/gpt/pretrain_gpt.py``'s ``main()``: 24 steps on one chip
           (12 on four), checked from its ``--metrics-jsonl`` stream
  server   ``examples/serving/serve_gpt.py``'s ``main()`` under Poisson load,
           then one fixed prompt whose per-step decode logits must agree with
           a full forward pass on the same weights

One process: a chip belongs to one process at a time. No arguments. Prints one
line per phase and, as the LAST line of stdout, one JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase: non-zero exit and no JSON. Off the chip it fails at the
device phase without training anything.
"""

import contextlib
import gc
import importlib.util
import io
import json
import math
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: GPT-2 345M (BASELINE.json config 5), the width every phase runs at
GPT2_345M = dict(layers=24, hidden=1024, heads=16, vocab=50304)
SEQ_LEN = 1024
#: one chip holds micro 4 x 2 microbatches: compiled for the v5e the step
#: needs 3.97 GiB of state + 8.35 GiB of temporaries at global batch 8, and
#: 10.83 GiB of temporaries at 16 (the microbatches are vmapped, so
#: temporaries grow with the global batch) — 14.8 of 16 GiB is no margin
MICRO_BATCH = 4
ONE_CHIP_GLOBAL_BATCH = 8
#: the seeded synthetic corpus holds 195 samples of 1024 tokens; 192 of them
#: are 24 steps at global batch 8 and 12 at 16 — at batch 8 the loss is
#: noisy enough that 12 steps clear their own first value by only 0.02
TRAIN_SAMPLES = 192
#: bf16 keeps 8 significand bits; a decode-path logit and the full-forward
#: logit differ by reduction order through every layer, so the bound is a
#: few ulps of the row's scale, fixed here and not tuned to a run
BF16_EPS = 2.0 ** -8
LOGIT_TOL_ULPS = 16


class PhaseFailed(Exception):
    """A phase's check did not hold; the message says which."""


def _require(cond, message):
    if not cond:
        raise PhaseFailed(message)


def load_example(relpath):
    """Import an example script as a module (examples/ is not a package)."""
    path = os.path.join(HERE, relpath)
    name = "_chip_smoke_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


class _CompileLedger:
    """Process-wide compile accounting off ``jax.monitoring``: how many
    programs were requested, how many the persistent cache served, and the
    seconds spent in backend compiles (a cache hit spends almost none)."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += float(duration)

    def snapshot(self):
        return self.requests, self.hits, self.compile_s

    def since(self, snap):
        r, h, s = self.snapshot()
        return (f"{r - snap[0]} programs, {h - snap[1]} from the compile "
                f"cache, {s - snap[2]:.1f} s compiling")


# -- device ------------------------------------------------------------------


def device_gate():
    """The accelerator, as JAX reports it — or PhaseFailed. Nothing turns
    this off: no flag, no environment variable, and it sets no platform."""
    import jax

    from apex_tpu import monitor
    from apex_tpu.ops._dispatch import on_tpu, resolve_impl

    devices = jax.devices()  # a backend that fails to start raises here
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)}", flush=True)
    _require(dev.platform == "tpu",
             f"no accelerator: JAX's default platform is {dev.platform!r}")
    _require(on_tpu(), "ops._dispatch.on_tpu() is false on a TPU")
    _require(resolve_impl("auto") == (True, False),
             f"resolve_impl('auto') = {resolve_impl('auto')}: ops would not "
             "run as compiled Pallas kernels")
    _require("APEX_TPU_PEAK_FLOPS" not in os.environ,
             "APEX_TPU_PEAK_FLOPS is set: MFU would be against a pinned peak")
    peak = monitor.peak_flops_per_device()
    _require(peak is not None,
             f"device kind {dev.device_kind!r} is not in the peak-FLOPs "
             "table (monitor/flops.py)")
    print(f"device: ok — peak {peak / 1e12:.0f} TFLOP/s bf16 per chip "
          "(monitor/flops.py)", flush=True)
    return info, peak


# -- clock -------------------------------------------------------------------


def clock_phase(peak_flops, n=4096, chain=128):
    """Time a chain of ``chain`` data-dependent n^3 bf16 matmuls with the
    host clock around ``block_until_ready``. A rate above the chip's peak
    means the wait does not wait, and every later step time is void."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), jnp.bfloat16)
    # variance-preserving, so the chain neither overflows nor dies out
    b = (jax.random.normal(jax.random.fold_in(key, 1), (n, n), jnp.float32)
         / math.sqrt(n)).astype(jnp.bfloat16)

    @jax.jit
    def run(a, b):
        return jax.lax.fori_loop(
            0, chain, lambda _, x: jnp.dot(x, b).astype(jnp.bfloat16), a)

    run(a, b).block_until_ready()  # compile + warm
    flops = 2.0 * n ** 3 * chain
    waited, fetched = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        out = run(a, b)
        out.block_until_ready()
        waited.append(time.perf_counter() - t0)
        # the same work ended by a host fetch of one element: completion
        # cannot be claimed before the value exists
        t0 = time.perf_counter()
        float(run(a, b)[0, 0])
        fetched.append(time.perf_counter() - t0)
    rate, rate_fetch = flops / min(waited), flops / min(fetched)
    print(f"clock: {chain} x {n}^3 bf16 matmul chain — "
          f"{rate / 1e12:.1f} TFLOP/s by block_until_ready, "
          f"{rate_fetch / 1e12:.1f} TFLOP/s by host fetch, "
          f"peak {peak_flops / 1e12:.0f}", flush=True)
    _require(math.isfinite(float(out[0, 0].astype(jnp.float32))),
             "matmul chain produced a non-finite value")
    _require(rate <= peak_flops,
             f"matmul chain 'ran' at {rate / 1e12:.1f} TFLOP/s, above the "
             f"chip's {peak_flops / 1e12:.0f}: block_until_ready returned "
             "before the device finished")
    print("clock: ok — block_until_ready waits for the device", flush=True)
    return rate


# -- trainer -----------------------------------------------------------------


def _print_peak_bytes(label):
    import jax

    from apex_tpu.monitor.xray.hbm.live import device_watermarks

    peaks = []
    for d in jax.devices():
        wm = device_watermarks(d)
        peaks.append(None if wm is None else wm["peak_bytes_in_use"])
    shown = ", ".join(
        "n/a" if p is None else f"{p / 2**30:.2f} GiB" for p in peaks)
    print(f"{label}: peak_bytes_in_use per device: [{shown}]", flush=True)
    return peaks


def lower_train_step(gpt, argv):
    """The training step these arguments build, lowered from abstract
    state (nothing is allocated). ``tpu_custom_call`` sites in its text are
    the Mosaic kernels: they are in the program or they are not, and no
    flag is consulted. ``benchmarks/tpu_preflight.py`` compiles the same
    object with no chip attached."""
    import jax

    from apex_tpu.training import build_gpt_training

    args = gpt.parse_args(argv)
    training = build_gpt_training(gpt.target_config(args, journal_on=False))
    state = jax.eval_shape(training.init_state)
    bag = jax.eval_shape(training.init_bag)
    scalar = jax.ShapeDtypeStruct((), "float32")
    batch = training.batch_struct()
    return training.train_step.lower(
        *state, bag, batch, batch, scalar, scalar)


def trainer_argv(jsonl, n_devices, model=GPT2_345M, seq_len=SEQ_LEN,
                 micro_batch=MICRO_BATCH, global_batch=None, extra_args=()):
    """The GPT example's command line for this machine. The global batch
    follows the device count (the builder wants a multiple of
    micro_batch x dp): 8 on one chip, one microbatch per chip beyond; the
    step count follows the global batch (``TRAIN_SAMPLES``)."""
    if global_batch is None:
        global_batch = max(ONE_CHIP_GLOBAL_BATCH, micro_batch * n_devices)
    steps = TRAIN_SAMPLES // global_batch
    return [
        "--layers", str(model["layers"]), "--hidden", str(model["hidden"]),
        "--heads", str(model["heads"]), "--vocab", str(model["vocab"]),
        "--seq-len", str(seq_len), "--micro-batch", str(micro_batch),
        "--global-batch", str(global_batch), "--steps", str(steps),
        "--log-interval", "1", "--metrics-jsonl", jsonl, *extra_args,
    ]


def trainer_phase(out_dir, **argv_kw):
    """Run the GPT example's own ``main()`` and check its metrics stream."""
    import jax

    from apex_tpu import _native
    from apex_tpu.ops._dispatch import on_tpu

    gpt = load_example("examples/gpt/pretrain_gpt.py")
    jsonl = os.path.join(out_dir, "trainer.jsonl")
    if os.path.exists(jsonl):
        os.unlink(jsonl)  # the sink appends; a stale run must not be read
    argv = trainer_argv(jsonl, len(jax.devices()), **argv_kw)
    parsed = gpt.parse_args(argv)
    vocab, steps = parsed.vocab, parsed.steps
    print(f"trainer: pretrain_gpt.main({' '.join(argv)})", flush=True)
    print("trainer: data path uses the "
          f"{'native C++ library' if _native.available() else 'numpy twin'} "
          "(apex_tpu/_native.py)", flush=True)

    kernels = lower_train_step(gpt, argv).as_text().count("tpu_custom_call")
    print(f"trainer: lowered step holds {kernels} tpu_custom_call sites "
          "(Mosaic kernels)", flush=True)
    _require((kernels > 0) == on_tpu(),
             f"lowered step holds {kernels} Mosaic kernels with "
             f"on_tpu()={on_tpu()}")

    rc = gpt.main(argv)
    _require(rc == 0, f"pretrain_gpt.main returned {rc}")
    gc.collect()  # the run's state is garbage now; free its HBM
    _print_peak_bytes("trainer")

    records = _read_jsonl(jsonl)
    metrics = [r for r in records if r["kind"] == "metrics"]
    _require(len(metrics) >= steps,
             f"{len(metrics)} metrics records for {steps} steps")
    losses = [r["loss"] for r in metrics]
    print("trainer: loss " + " ".join(f"{x:.4f}" for x in losses),
          flush=True)
    _require(all(isinstance(x, float) and math.isfinite(x) for x in losses),
             f"non-finite loss in {losses}")
    expect = math.log(vocab)
    _require(abs(losses[0] - expect) <= 0.5,
             f"first loss {losses[0]:.3f} is not within 0.5 of "
             f"ln(vocab) = {expect:.3f}")
    tail = sum(losses[-3:]) / 3
    _require(tail < losses[0],
             f"mean of the last three losses {tail:.4f} is not below the "
             f"first {losses[0]:.4f}")
    skipped = [r["skipped"] for r in metrics]
    first_clean = next(
        (i for i, s in enumerate(skipped) if not s), len(skipped))
    _require(not any(skipped[first_clean:]),
             f"updates skipped after the loss scale settled: {skipped}")
    _require(first_clean < len(skipped), "every update was skipped")
    recompiles = [r for r in records
                  if r["kind"] == "compile" and r.get("recompile")]
    _require(not recompiles,
             f"{len(recompiles)} post-warm-up recompile record(s): "
             f"{recompiles[:2]}")
    mfus = [r.get("mfu") for r in metrics]
    _require(all(isinstance(m, float) and 0.0 < m < 1.0 for m in mfus),
             f"mfu not in (0, 1) on every record: {mfus}")
    setup = sum(r["dur_s"] for r in records
                if r["kind"] == "span" and r["phase"] in ("init", "compile"))
    steady = metrics[-1]
    print(f"trainer: ok — {len(metrics)} steps, set-up (init + first step) "
          f"{setup:.1f} s, last step {steady['step_ms']:.0f} ms, "
          f"{steady['tokens_per_s']:.0f} tokens/s, mfu {steady['mfu']:.3f}, "
          f"loss scale {steady['loss_scale']:.0f}, "
          f"{int(sum(skipped))} skipped", flush=True)
    return losses


# -- server ------------------------------------------------------------------


def logits_config(args):
    """The ``collect_logits`` engine of the logits check: the served
    geometry with a single prefill bucket (two programs to compile, not
    six)."""
    from apex_tpu.serving import ServingConfig

    return ServingConfig(
        lanes=args.lanes, block_size=args.block_size, num_blocks=args.blocks,
        max_seq_len=args.max_seq_len,
        prefill_buckets=(2 * args.block_size,),
        seed=args.seed, collect_logits=True,
    )


def reference_forward(model):
    """The full forward pass the decode logits are held against: all
    positions at once, no cache — one jitted program."""
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda variables, tokens: model.apply(variables, tokens).astype(
            jnp.float32))


def _logits_check(serve, argv):
    """One fixed prompt through a ``collect_logits`` engine on the served
    model: every decode step's next-token logits against a full forward
    pass over the same tokens, within bf16 tolerance."""
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.serving import ServingEngine

    args = serve.parse_args(argv)
    model, variables = serve.build_model(args)
    # 24 + 8 tokens: the reference forward runs at a sublane-aligned length
    prompt = np.random.RandomState(args.seed).randint(
        0, args.vocab, size=24).astype(np.int32)
    eng = ServingEngine(model, variables, logits_config(args)).start()
    req = eng.submit(prompt, max_new_tokens=8)
    for _ in range(64):
        if eng.idle:
            break
        eng.tick()
    _require(req.state == "completed" and len(req.logits) == 8,
             f"logits request ended {req.state!r} with "
             f"{len(req.logits or [])} logit rows")
    seq = np.concatenate([prompt, req.tokens_out]).astype(np.int32)
    full = np.asarray(
        reference_forward(model)(variables, jnp.asarray(seq)[None]))[0]
    worst, agree = 0.0, 0
    for i, row in enumerate(req.logits):
        ref = full[len(prompt) - 1 + i]
        _require(row.shape == ref.shape and np.all(np.isfinite(row)),
                 f"decode logits row {i}: shape {row.shape}, non-finite")
        scale = max(1.0, float(np.max(np.abs(ref))))
        worst = max(worst, float(np.max(np.abs(row - ref))) / scale)
        agree += int(np.argmax(row) == np.argmax(ref))
    tol = LOGIT_TOL_ULPS * BF16_EPS
    print(f"server: decode logits vs full forward — max error "
          f"{worst:.4f} of the row scale (bound {tol:.4f}), argmax agrees "
          f"on {agree}/8 steps", flush=True)
    _require(worst <= tol,
             f"decode logits differ from the full forward by {worst:.4f} of "
             f"the row scale, above the bf16 bound {tol:.4f}")


def server_argv(jsonl, model=GPT2_345M, lanes=8, block_size=16,
                max_seq_len=256, requests=16, prompt_len=(8, 200),
                max_new=(4, 16)):
    """The serving example's command line: ``max_seq_len`` 256 holds the
    prefill buckets to five (16 ... 256), and the prompt range reaches
    every one of them."""
    return [
        "--layers", str(model["layers"]), "--hidden", str(model["hidden"]),
        "--heads", str(model["heads"]), "--vocab", str(model["vocab"]),
        "--lanes", str(lanes), "--block-size", str(block_size),
        # every lane can hold a longest request, twice over
        "--blocks", str(2 * lanes * max_seq_len // block_size),
        "--max-seq-len", str(max_seq_len),
        "--requests", str(requests), "--rate", "20",
        "--queue-depth", str(2 * requests),
        "--prompt-len", str(prompt_len[0]), str(prompt_len[1]),
        "--max-new", str(max_new[0]), str(max_new[1]),
        "--metrics-jsonl", jsonl,
    ]


def server_phase(out_dir, **argv_kw):
    """Run the serving example's own ``main()`` under Poisson load, check
    its summary, then the logits agreement."""
    serve = load_example("examples/serving/serve_gpt.py")
    jsonl = os.path.join(out_dir, "server.jsonl")
    if os.path.exists(jsonl):
        os.unlink(jsonl)
    argv = server_argv(jsonl, **argv_kw)
    requests = serve.parse_args(argv).requests
    print(f"server: serve_gpt.main({' '.join(argv)})", flush=True)

    # the summary lines are the example's user-facing result: read them as
    # a user would, while still showing everything it prints
    captured, shown = io.StringIO(), sys.stdout

    class _Tee(io.TextIOBase):
        def write(self, text):
            captured.write(text)
            return shown.write(text)

        def flush(self):
            shown.flush()

    with contextlib.redirect_stdout(_Tee()):
        rc = serve.main(argv)
    _require(rc == 0, f"serve_gpt.main returned {rc}")
    out = captured.getvalue()
    summary = re.search(
        r"serving summary: submitted (\d+) completed (\d+) rejected (\d+) "
        r"timed_out (\d+) cancelled (\d+) failed (\d+)", out)
    compiles = re.search(r"steady-state compiles (\d+)", out)
    _require(summary and compiles, "no serving summary line in the output")
    submitted, completed, rejected, timed_out, cancelled, failed = map(
        int, summary.groups())
    _require(submitted >= requests and completed == submitted,
             f"not every request completed: {summary.group(0)}")
    _require(rejected + timed_out + cancelled + failed == 0,
             f"requests lost: {summary.group(0)}")
    _require(int(compiles.group(1)) == 0,
             f"{compiles.group(1)} compiles after the engine started")
    records = _read_jsonl(jsonl)
    terminal = {r["id"] for r in records
                if r["kind"] == "request" and r["state"] == "completed"}
    _require(len(terminal) == submitted,
             f"{len(terminal)} completed request records for {submitted}")
    setup = sum(r["dur_s"] for r in records
                if r["kind"] == "span" and r["phase"] in ("init", "compile"))
    gc.collect()

    _logits_check(serve, argv)
    print(f"server: ok — {completed}/{submitted} requests completed, "
          f"set-up (init + compile) {setup:.1f} s, 0 steady-state compiles",
          flush=True)


# -- main --------------------------------------------------------------------


def main():
    t_start = time.perf_counter()
    out_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    try:
        # first, before any compile: compiled programs persist across runs
        from apex_tpu.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        ledger = _CompileLedger()
        info, peak = device_gate()
        os.makedirs(out_dir, exist_ok=True)
        print(f"compile cache: {cache_dir}", flush=True)
        clock_phase(peak)
        for name, phase in (("trainer", trainer_phase),
                            ("server", server_phase)):
            t0, snap = time.perf_counter(), ledger.snapshot()
            phase(out_dir)
            print(f"{name}: {time.perf_counter() - t0:.1f} s wall — "
                  f"{ledger.since(snap)}", flush=True)
        _print_peak_bytes("process")
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED — {e}", flush=True)
        return 1
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t_start:.0f} s", flush=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
