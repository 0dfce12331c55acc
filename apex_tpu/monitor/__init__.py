"""Unified training telemetry: in-step taps -> host router -> sinks.

The observability layer over L2-L4 of the stack (SURVEY map): the
production-pretraining counterpart of TorchTitan's built-in metrics/MFU/
profiling subsystem (PAPERS.md). Four cooperating pieces:

- ``metrics``  — :class:`MetricBag`, a jit-compatible flax.struct pytree of
  named scalar aggregates that lives INSIDE the compiled train step and is
  fetched to host once per log interval, so the device-to-host sync
  (which stalls the dispatch pipeline) is paid O(1/interval), not per
  step. Plus grad-norm helpers and the reader for
  ``sow("intermediates", ...)`` taps.
- ``router``   — :class:`MetricRouter` fanning one shared record schema
  (``make_record``) out to pluggable sinks: jsonl, CSV, stdout,
  TensorBoard-if-importable, in-memory. ``Timers.write``, the resilience
  anomaly log, and the examples all emit through it.
- ``flops``    — analytic model-FLOPs counters for the GPT/BERT testing
  models and the MFU / tokens-per-second arithmetic.
- ``watchdog`` — :class:`StallWatchdog` (heartbeat thread flagging a step
  that exceeds its deadline; complements the SIGTERM-driven resilience
  path, which only helps when the cluster TELLS us something died — its
  ``escalations`` ladder carries the hung-job incident response in
  ``apex_tpu.resilience.health``: warn -> forensic dump -> coordinated
  self-termination) and :class:`ProfilerTrigger` (snapshots a
  ``jax.profiler`` trace window at a requested step or when the anomaly
  sentinel escalates).
- ``taps``     — the registered-taps table every ``sow`` name used in
  ``apex_tpu/`` must appear in (lint-tested, so a layer refactor cannot
  silently drop a metric).
- ``xray``     — execution introspection of the compiled step itself:
  the collective-traffic ledger (instrumented ``lax`` collective
  wrappers + per-axis byte totals + ICI roofline), XLA memory reports
  (args/outputs/temps vs device headroom), and the recompile sentinel
  (:class:`~apex_tpu.monitor.xray.CompileWatcher`) — all emitting
  ``kind="comms"/"memory"/"compile"`` records through the router.
- ``goodput``  — the RUN-level ledger over everything above: phase spans
  (``kind="span"``: init/compile/data_wait/step/ckpt/rollback/stall/
  incident/shutdown) + run headers joining restart incarnations, the
  goodput/badput accountant, the fleet-health divergence detector (plus
  its in-job ``LiveFleetMonitor``), and the perf-regression sentinel
  (``python -m apex_tpu.monitor.goodput``).

See docs/observability.md for the end-to-end wiring.

Attribute access is lazy (PEP 562, the ``analysis`` package's contract):
importing this package must not initialize jax, so the jax-free
consumers — ``xray.timeline``'s trace analyzer and the ``router``
record schema — stay importable on a box with no jax at all
(docs/benchmarking.md: a capture is analyzable offline, anywhere).
"""

_EXPORTS = {
    # metrics (jax + flax)
    "MetricBag": "metrics",
    "metric_bag": "metrics",
    "reset_bag": "metrics",
    "read_bag": "metrics",
    "host_fetch_count": "metrics",
    "global_grad_norm": "metrics",
    "per_layer_grad_norms": "metrics",
    "taps_from_intermediates": "metrics",
    # router (jax-free)
    "MetricRouter": "router",
    "Sink": "router",
    "JsonlSink": "router",
    "CsvSink": "router",
    "StdoutSink": "router",
    "MemorySink": "router",
    "make_record": "router",
    "try_tensorboard_sink": "router",
    # flops (jax only for device-kind lookup, on use)
    "transformer_layer_flops_per_token": "flops",
    "gpt_flops_per_token": "flops",
    "bert_flops_per_token": "flops",
    "training_flops_per_step": "flops",
    "tokens_per_second": "flops",
    "mfu": "flops",
    "peak_flops_per_device": "flops",
    # watchdog / profiler trigger
    "StallWatchdog": "watchdog",
    "ProfilerTrigger": "watchdog",
    # registered-taps table (jax-free)
    "REGISTERED_TAPS": "taps",
}

__all__ = sorted(_EXPORTS) + [
    "metrics", "router", "flops", "watchdog", "taps", "xray", "goodput",
]

_SUBMODULES = frozenset(__all__) - frozenset(_EXPORTS)


def __getattr__(name):
    import importlib

    if name in _EXPORTS:
        mod = importlib.import_module(f"apex_tpu.monitor.{_EXPORTS[name]}")
        return getattr(mod, name)
    if name in _SUBMODULES:
        return importlib.import_module(f"apex_tpu.monitor.{name}")
    raise AttributeError(f"module 'apex_tpu.monitor' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
