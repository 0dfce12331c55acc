"""apex_tpu — a TPU-native training-acceleration framework.

A ground-up re-design of the capabilities of NVIDIA Apex (reference:
/root/reference, see SURVEY.md) for TPUs: JAX/XLA for the compute path, Pallas
for fused kernels, ``jax.sharding.Mesh`` + ``shard_map`` collectives over ICI
for every flavor of parallelism, and functional (pytree-based) state instead
of in-place tensor mutation.

Subpackage map (reference parity noted per module):

- ``apex_tpu.amp``          — mixed precision (ref: apex/amp, apex/fp16_utils)
- ``apex_tpu.ops``          — fused ops / Pallas kernels (ref: csrc/, apex/normalization,
                              apex/mlp, apex/fused_dense, apex/transformer/functional)
- ``apex_tpu.optimizers``   — fused + distributed optimizers (ref: apex/optimizers,
                              apex/contrib/optimizers)
- ``apex_tpu.parallel``     — data/tensor/pipeline/sequence/context parallelism
                              (ref: apex/parallel, apex/transformer)
- ``apex_tpu.transformer``  — Megatron-style transformer building blocks
                              (ref: apex/transformer)
- ``apex_tpu.contrib``      — contrib zoo parity (ref: apex/contrib)
- ``apex_tpu.models``       — flagship models (GPT, BERT, ResNet) used by the
                              examples / benchmarks (ref: apex/examples, testing/standalone_*)
- ``apex_tpu.training``     — the GPT training step, built one way
                              (``build_gpt_training``): what the benchmark's
                              cells, ``chip_smoke.py``, the GPT example and
                              the replayer all run
- ``apex_tpu.resilience``   — training resilience: anomaly sentinel, in-memory
                              rollback, checkpoint integrity manifests, fault
                              injection (no reference equivalent; the recovery
                              layer production pretraining needs)
- ``apex_tpu.monitor``      — unified training telemetry: in-step metric taps
                              (MetricBag), pluggable metric sinks, MFU /
                              throughput, stall watchdog, on-anomaly profiler
                              capture (no reference equivalent; see
                              docs/observability.md)
- ``apex_tpu.analysis``     — trace-time static analysis: jaxpr auditors
                              (precision / donation / collective-safety /
                              host-sync) + a unified AST lint framework and
                              the ``python -m apex_tpu.analysis`` gate (no
                              reference equivalent; see docs/analysis.md)
- ``apex_tpu.serving``      — overload-hardened inference serving:
                              continuous batching over a block-allocated
                              KV pool, bounded admission + load shedding,
                              per-request deadlines, graceful drain (no
                              reference equivalent — the reference has no
                              serving layer; see docs/serving.md)
"""

import logging

__version__ = "0.1.0"


class RankInfoFormatter(logging.Formatter):
    """Log formatter that prefixes records with JAX process/device info.

    TPU-native analogue of the reference's rank-aware formatter
    (ref: apex/__init__.py:31-43) — torch.distributed rank/world is replaced
    by the JAX multi-controller process index.
    """

    def format(self, record):
        try:
            import jax

            rank_info = f"[process {jax.process_index()}/{jax.process_count()}]"
        except Exception:  # pragma: no cover - jax not initialized yet
            rank_info = "[process ?/?]"
        record.rank_info = rank_info
        return super().format(record)


_logger = logging.getLogger("apex_tpu")
if not _logger.handlers:
    _handler = logging.StreamHandler()
    _handler.setFormatter(
        RankInfoFormatter("%(asctime)s %(rank_info)s %(name)s %(levelname)s: %(message)s")
    )
    _logger.addHandler(_handler)
    _logger.propagate = False


def get_logger(name: str = "apex_tpu") -> logging.Logger:
    return logging.getLogger(name)


def set_logging_level(level) -> None:
    """Set the library-wide logging level (ref: transformer/log_util.py:10)."""
    _logger.setLevel(level)


def deprecated_warning(msg: str) -> None:
    """Emit a deprecation warning once (ref: apex/__init__.py:62)."""
    import warnings

    warnings.warn(msg, FutureWarning, stacklevel=2)


# Lazy subpackage attributes (PEP 562), keeping the reference's top-level
# surface (apex/__init__.py: __all__ = amp, fp16_utils, optimizers,
# normalization, transformer [+ parallel]) so `import apex_tpu;
# apex_tpu.amp.initialize(...)` works like `import apex; apex.amp...` —
# but WITHOUT importing jax at `import apex_tpu` time: the jax-free
# corners (analysis HLO parser, monitor router, xray.timeline's trace
# analyzer) must stay importable on a box with no jax, and the analysis
# CLI must be able to force its CPU topology before jax initializes.
_SUBPACKAGES = frozenset({
    "amp", "fp16_utils", "monitor", "normalization", "optimizers",
    "parallel", "resilience", "serving", "transformer",
})


def __getattr__(name):
    if name in _SUBPACKAGES:
        import importlib

        return importlib.import_module(f"apex_tpu.{name}")
    raise AttributeError(f"module 'apex_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))


__all__ = [
    "amp",
    "fp16_utils",
    "monitor",
    "optimizers",
    "normalization",
    "transformer",
    "parallel",
    "resilience",
    "serving",
    "get_logger",
    "set_logging_level",
    "deprecated_warning",
]
