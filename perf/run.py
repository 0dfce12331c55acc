#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Everything a cell is made of is data the harness finds by name:
``BENCHMARK.json`` (the cell's configuration and which metrics it reports),
``perf/workloads/<cell>.json`` (driver, traffic parameters, limits of the
output check), ``perf/configs/<config>.json`` (sizes), ``perf/drivers/
<driver>.py`` (set-up, window, output check) and ``perf/layer_metrics/
<metric>.py`` (one reader each). This file knows no cell, configuration or
metric by name; a later PR adds files and ``BENCHMARK.json`` entries.

The order of a run: set-up (weights from the seed, every program compiled and
warmed; counted in ``setup_s``), the measured window of ``--seconds`` (no
compile may happen in it), the device's memory peak, the program's state
freed, then the comparison of what the timed path produced with the plain
reference, which decides ``correct``. ``--trace 1`` profiles a slice of the
window and reports the per-layer metrics in place of the end-to-end ones;
where the driver hands out the compiled step's text (``hlo_text``,
``scope_names``), the trace's device ops are booked to the program's own
names: the step's phases, the model's scopes, the kernels. A driver of
several programs (a serving engine) hands out ``{program: text}``, and each
program's ops are booked by its own text alone.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last the numbers compared beside their limits under ``compared``). Exit
code 0 whenever that line is printed; without an accelerator, or with fewer
chips than the cell asks for, it prints no result and exits 3.
"""

import time

_T_START = time.perf_counter()  # process start, as near as Python allows

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_DEVICE = 3


class Refused(Exception):
    """The run cannot be made here (no chip, unknown cell, missing file)."""


def _load_json(path):
    if not os.path.isfile(path):
        raise Refused(f"missing file: {path}")
    with open(path) as f:
        return json.load(f)


def load_module(root, *relpath):
    """Import ``<root>/perf/<relpath>.py`` under a name of its own. Files are
    found by path, so a metric named ``mfu_pct.train`` is a file of that
    name."""
    path = os.path.join(root, "perf", *relpath) + ".py"
    if not os.path.isfile(path):
        raise Refused(f"missing file: {path}")
    name = "perf_" + "_".join(relpath).replace(".", "_").replace("-", "_")
    if name in sys.modules and getattr(
            sys.modules[name], "__file__", None) == path:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _reports(metric, cell_name, cell_metrics):
    """Does ``cell_name`` report this ``BENCHMARK.json`` metric? A metric
    that lists ``workloads`` is reported by those; an end-to-end metric that
    lists none by every cell; a per-layer metric that lists none by every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in cell_metrics
    return True


class Context:
    """What the harness lends a driver and the metric readers: spans on the
    profiler's clock, the profiler's slice of the window, the peaks."""

    def __init__(self, root, cell, config, seed, seconds, trace, device,
                 peaks):
        self.root, self.cell, self.config = root, cell, config
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.device, self.peaks = device, peaks
        self.chips = int(cell.get("chips", 1))
        self.counters = {}      # filled by the driver's window
        #: a traced run's events (``trace_reduce.load_xplane``), loaded once
        #: after the state is freed, for the correctness check and reduction
        self.events = None
        #: seconds the workload's own readings of ``events`` took, by name
        self.trace_reads = {}
        self.reduction = None   # filled from the trace
        self._trace_dir = os.path.join(root, ".perf_trace")
        self._trace_state = "idle" if trace else "off"
        self._trace_t0 = self._window_t0 = None
        self._annotation = None
        #: how long starting the profiler stalled the host (about 4 s on a
        #: v5e): rates over the window leave the stall out
        self.trace_stall_s = 0.0
        #: how long stopping it took, after the window (read, not used)
        self.trace_stop_s = 0.0

    def span(self, name):
        """A named host span. In a traced run it is written into the
        profiler's own trace, where gaps on the device are attributed to
        it; otherwise it costs nothing."""
        if self._trace_state == "on":
            return self._annotation(name)
        return contextlib.nullcontext()

    def window_started(self):
        self._window_t0 = time.perf_counter()

    def poll(self):
        """Called by the driver between steps or ticks. In a traced run the
        profiler covers the last ``trace_seconds`` of the ``--seconds`` (the
        stall of starting it left out): it starts that long before their
        end. It is never stopped here: stopping it takes seconds (PERF.md
        6), and a stop inside the driver's loop would count them into the
        window's length. ``run_cell`` stops it once the window has
        returned, so a traced window ends where a plain one does."""
        if self._trace_state != "idle":
            return
        elapsed = time.perf_counter() - self._window_t0
        if elapsed >= self.seconds - self.cell.get("trace_seconds", 3.0):
            self._start_trace()

    def _start_trace(self):
        import jax

        shutil.rmtree(self._trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # our own spans only: small and cheap
        opts.host_tracer_level = 2
        before = time.perf_counter()
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation
        self._trace_t0 = time.perf_counter()
        self.trace_stall_s = self._trace_t0 - before
        self._trace_state = "on"

    def stop_trace(self):
        if self._trace_state != "on":
            return
        import jax

        before = time.perf_counter()
        jax.profiler.stop_trace()
        self.trace_stop_s = time.perf_counter() - before
        self._trace_state = "done"

    def trace_file(self):
        found = sorted(glob.glob(os.path.join(
            self._trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def drop_trace(self):
        shutil.rmtree(self._trace_dir, ignore_errors=True)


def _enable_compile_cache(root):
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache's key), unless the machine's
    owner set ``JAX_COMPILATION_CACHE_DIR``: then no directory is set in
    code."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _device_entry(chips, allow_cpu):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not allow_cpu:
        raise Refused(f"no accelerator: JAX's platform is {dev.platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def _memory_peak(chips):
    """The peak on the fullest chip used, as far as the runtime tells it.
    The TPU's allocator keeps two peaks: ``peak_bytes_in_use`` (live
    buffers: state, inputs, outputs) and ``peak_bytes_reserved`` (what
    running programs reserved for their temporaries, which the first leaves
    out). The true peak is at least each and at most their sum; the larger
    is reported, so the figure never overstates."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak


def _say(msg):
    print(msg, file=sys.stderr, flush=True)


def run_cell(root, workload, seed, seconds, trace, allow_cpu=False,
             out=sys.stdout):
    """One run; returns the result dict it printed as the last line."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise Refused(f"no cell {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    cell = _load_json(os.path.join(root, "perf", "workloads",
                                   workload + ".json"))
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    chips = int(entry["chips"])

    e2e = [m for m in bench["end_to_end"] if _reports(m, workload, ())]
    e2e_names = [m["name"] for m in e2e]
    layer = [m for m in bench["per_layer"]
             if _reports(m, workload, e2e_names)]

    if root not in sys.path:
        sys.path.insert(0, root)  # the system under test is imported from here
    device = _device_entry(chips, allow_cpu)
    peaks = None
    if device["platform"] == "tpu":  # a CPU run is a test: it keeps no cache
        _enable_compile_cache(root)
        peaks = load_module(root, "flops").peaks_for(device["kind"])
    ledger = load_module(root, "compile_ledger").CompileLedger()
    ctx = Context(root, cell, config, seed, seconds, trace, device, peaks)
    driver = load_module(root, "drivers", cell["driver"])

    state = driver.setup(cell, config, seed, ctx)
    setup_compiles = ledger.since((0, 0, 0.0))
    snap = ledger.snapshot()
    setup_s = time.perf_counter() - _T_START
    ctx.window_started()
    try:
        result = driver.window(state, seconds, ctx)
    finally:
        ctx.stop_trace()
    in_window = ledger.since(snap)
    _say(f"[run] set-up {setup_s:.1f} s: {setup_compiles[0]} programs, "
         f"{setup_compiles[1]} from the compile cache, "
         f"{setup_compiles[2]:.1f} s compiling; in the window: "
         f"{in_window[0]} programs requested")

    device["memory_peak_bytes"] = _memory_peak(chips)
    # the compiled step's text names each device op's phase, model scope and
    # kernel; taken while the state still holds the compiled step
    before = time.perf_counter()
    hlo_text = (driver.hlo_text(state)
                if trace and hasattr(driver, "hlo_text") else None)
    names_s = time.perf_counter() - before
    driver.release(state)
    gc.collect()

    if trace:
        reducer = load_module(root, "trace_reduce")
        path = ctx.trace_file()
        if path is None:
            raise RuntimeError("the profiler left no trace")
        before = time.perf_counter()
        ctx.events = reducer.load_xplane(path)
        load_s = time.perf_counter() - before
    before = time.perf_counter()
    compared = list(driver.check(state, ctx))
    check_s = time.perf_counter() - before
    compared.append({"name": "compiles_in_window", "value": in_window[0],
                     "limit": 0})
    correct = load_module(root, "compare").correct(compared)

    measured = dict(result["end_to_end"], setup_s=setup_s)
    ctx.counters = result.get("counters", {})
    # rates are over the window less the stall of starting the profiler
    ctx.counters["window_s"] = result["window_s"] - ctx.trace_stall_s
    metrics, breakdown = {}, None
    if trace:
        before = time.perf_counter()
        scopes = program_scopes = None
        if hlo_text is not None:
            scope_map = load_module(root, "hlo_scopes").scope_map
            names = driver.scope_names()
            if isinstance(hlo_text, dict):
                # several programs, each booked by its own text
                program_scopes = {p: scope_map(t, **names)
                                  for p, t in hlo_text.items()}
            else:
                scopes = scope_map(hlo_text, **names)
            del hlo_text
        names_s += time.perf_counter() - before
        before = time.perf_counter()
        ctx.reduction = reducer.reduce(
            ctx.events, chips=chips, spans=cell.get("spans", ()),
            device_required=device["platform"] == "tpu", scopes=scopes,
            program_scopes=program_scopes)
        reduce_s = time.perf_counter() - before
        n_events, ctx.events = len(ctx.events), None
        ctx.drop_trace()
        reads = "".join(f", {k} {v:.2f} s"
                        for k, v in ctx.trace_reads.items())
        _say(f"[run] starting the profiler stalled {ctx.trace_stall_s:.2f} s"
             f", stopping it took {ctx.trace_stop_s:.2f} s (after the "
             f"window); the trace: loaded once in {load_s:.2f} s ({n_events}"
             f" events), reduced in {reduce_s:.2f} s; the correctness check "
             f"read it{reads or ' not at all'}; the compiled step's text and "
             f"its scope map took {names_s:.2f} s")
        if ctx.reduction["busy_s"] is not None:
            device["busy_s"] = ctx.reduction["busy_s"]
            device["window_s"] = ctx.reduction["window_s"]
        breakdown = {"device_ops": ctx.reduction["top_ops"][:10],
                     "idle_gaps": ctx.reduction["idle_gaps"][:10]}
        for m in layer:
            value = load_module(root, "layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}

    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    _say(f"[run] the output check took {check_s:.2f} s; the result after "
         f"{time.perf_counter() - _T_START:.1f} s from process start")
    for c in compared:
        _say(f"[compared] {c['name']} = {c['value']:.6g} "
             f"(limit {c['limit']:.6g})"
             + ("" if c["value"] <= c["limit"] else "  <-- OVER"))
    print(json.dumps(line), file=out, flush=True)
    return line


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        run_cell(ROOT, args.workload, args.seed, args.seconds, args.trace)
    except Refused as e:
        _say(f"[run] refused: {e}")
        return NO_DEVICE
    return 0


if __name__ == "__main__":
    sys.exit(main())
