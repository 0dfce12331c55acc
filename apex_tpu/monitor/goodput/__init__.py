"""Run-level goodput: span ledger, accountant, fleet health, perf gate.

The run-lifecycle layer of the observability stack (docs/observability.md
"Goodput & fleet health"). Cooperating pieces, all through the
shared MetricRouter record schema:

- ``spans``      — the ``kind="span"`` phase ledger (closed taxonomy
  :data:`~apex_tpu.monitor.goodput.spans.PHASES`), ``kind="run"``
  incarnation headers, and the torn-stream teardown flush.
- ``scopes``     — the device-side counterpart of the phase ledger: the
  closed registries of a training step's phases (``jax.named_scope``)
  and of the Pallas kernels' names (``kernel_metadata``), which the
  timeline reader joins a profiler capture to.
- ``accountant`` — replays one or more streams (multiple incarnations,
  multiple hosts) into a goodput/badput partition whose identity
  ``productive + Σ badput + unattributed == wall`` is exact.
- ``fleet``      — straggler hosts (robust z-score on step duration) and
  silent-corruption suspects (cross-host replicated-value mismatch).
- ``live``       — the same fleet checks run IN the job over a rolling
  MemorySink window (``LiveFleetMonitor``), emitting ``kind="fleet"``
  records while running instead of only offline.
- ``sentinel``   — the perf-regression gate over the BENCH trajectory
  (``python -m apex_tpu.monitor.goodput --check``).

Attribute access is lazy (PEP 562, the monitor-package contract) and
every submodule is jax-free: a stream is accountable, and the gate
runnable, on a box with no jax at all.
"""

_EXPORTS = {
    # spans
    "PHASES": "spans",
    "PHASE_PRIORITY": "spans",
    "PRODUCTIVE_PHASE": "spans",
    "PRODUCTIVE_PHASES": "spans",
    "Span": "spans",
    "span": "spans",
    "begin_span": "spans",
    "emit_span": "spans",
    "run_header": "spans",
    "derive_run_id": "spans",
    "set_router": "spans",
    "get_router": "spans",
    "flush_open_spans": "spans",
    # scopes
    "STEP_PHASES": "scopes",
    "KERNELS": "scopes",
    "step_phase": "scopes",
    "kernel_metadata": "scopes",
    # accountant
    "GoodputReport": "accountant",
    "account": "accountant",
    "read_records": "accountant",
    "BADPUT_PHASES": "accountant",
    # fleet
    "FleetReport": "fleet",
    "detect_divergence": "fleet",
    "LiveFleetMonitor": "live",
    # sentinel
    "measurements_from_records": "sentinel",
    "noise_tolerance": "sentinel",
    "check_regression": "sentinel",
    "goodput_allowlist": "sentinel",
}

__all__ = sorted(_EXPORTS) + [
    "spans", "scopes", "accountant", "fleet", "live", "sentinel",
]

_SUBMODULES = frozenset(__all__) - frozenset(_EXPORTS)


def __getattr__(name):
    import importlib

    if name in _EXPORTS:
        mod = importlib.import_module(
            f"apex_tpu.monitor.goodput.{_EXPORTS[name]}"
        )
        return getattr(mod, name)
    if name in _SUBMODULES:
        return importlib.import_module(f"apex_tpu.monitor.goodput.{name}")
    raise AttributeError(
        f"module 'apex_tpu.monitor.goodput' has no attribute {name!r}"
    )


def __dir__():
    return sorted(set(globals()) | set(__all__))
