"""Device-side names: the phases of a training step and the Pallas kernels.

``spans.PHASES`` names where the JOB's wall clock goes, on the host. The
two registries here name where the STEP's device time goes, so that a
profiler capture speaks the program's vocabulary instead of XLA's
(``fusion.895``, ``copy.12``):

- :data:`STEP_PHASES` — the parts of ``train_step``
  (``apex_tpu/training/gpt_step.py``), opened with :func:`step_phase`, a
  ``jax.named_scope``. The scope lands in every traced op's ``op_name``
  path (``jit(train_step)/.../forward_backward/...``), which the compiled
  HLO keeps in each instruction's ``metadata``. JAX itself marks the
  backward pass's ops with ``transpose(jvp(...))`` in the same path, so
  ``forward_backward`` splits into forward and backward with no scope of
  its own.
- :data:`KERNELS` — one name per ``pl.pallas_call`` in the tree, attached
  with :func:`kernel_metadata`. It lands in the custom-call's
  ``frontend_attributes={kernel_metadata={"kernel": ...}}``, which a TPU
  trace prints as part of the op's text.

Both are compile-time names: nothing here runs on the device, and a step
compiled with them differs from one without only in op metadata.

Closed like ``spans.PHASES`` and for the same reason: a table of device
time by phase is only comparable across runs if every run buckets the
same way. :func:`step_phase` and :func:`kernel_metadata` raise on a name
outside the registry, and ``tests/test_scopes.py`` holds every
``pallas_call`` and every scope of the step to it.

The reader is ``monitor/xray/timeline`` (``scope_map``); jax-free itself,
this module imports jax only inside :func:`step_phase`.
"""

__all__ = [
    "STEP_PHASES",
    "MODEL_SCOPES",
    "KERNELS",
    "KERNEL_KEY",
    "step_phase",
    "model_scope",
    "kernel_metadata",
]

#: The phases of one training step, in the order the step runs them.
#:
#: - ``forward_backward`` — the ``value_and_grad`` over the microbatches:
#:   loss scaling, the model's forward, and its transpose (``backward``
#:   to the reader: the ops whose path holds ``transpose(``)
#: - ``grad_sync``        — the dp gradient all-reduce (under ZeRO the
#:   optimizer's own reduce-scatter does this inside ``optimizer``)
#: - ``unscale``          — ``scaler.unscale`` with its overflow check
#:   over every gradient, and ``scaler.update``
#: - ``optimizer``        — the ``apply`` branch of the gated ``cond``:
#:   the optimizer's update and ``apply_updates``
#: - ``guard``            — what watches the step: the sentinel's gate and
#:   update, the non-finite check of the new parameters, the MetricBag
#:   taps with the tp-aware gradient norm, the per-layer RMS reduction
STEP_PHASES = (
    "forward_backward",
    "grad_sync",
    "unscale",
    "optimizer",
    "guard",
)

#: Parts of the MODEL inside ``forward_backward`` that no flax module path
#: tells apart: the reader buckets an op under the innermost of these in
#: its path (``monitor/xray/timeline`` ``scope_map``), beside its phase.
#:
#: - ``mla_project``  — latent attention's five projections, their two
#:   norms and the rope (everything of the module but the flash kernels)
#: - ``moe_route``    — the router's matmul, scores, top-k, gates
#: - ``moe_dispatch`` — the sort of the assignments, the gather of their
#:   rows and, over an expert axis, the exchange out
#: - ``moe_experts``  — the grouped matmuls of the routed experts
#: - ``moe_combine``  — rows back to their tokens, weighted by the gates
#:   (and the exchange back)
#: - ``moe_shared``   — the shared expert, computed for every token
#: - ``mtp``          — the multi-token-prediction block and its loss
MODEL_SCOPES = (
    "mla_project",
    "moe_route",
    "moe_dispatch",
    "moe_experts",
    "moe_combine",
    "moe_shared",
    "mtp",
)

#: Every Pallas kernel of the tree: ops/attention.py (flash forward, dq,
#: dk/dv), ops/layer_norm.py (LayerNorm and RMSNorm, forward and
#: backward), optimizers/_fused_kernels.py (flat Adam, flat sum of
#: squares); ``mla_rope`` is ops/attention.py's rotation of latent
#: attention's rope queries in the projection's own layout, and
#: ``paged_decode`` its decode attention over the serving engine's KV pool.
KERNELS = (
    "flash_fwd",
    "flash_bwd_dq",
    "flash_bwd_dkv",
    "mla_rope",
    "paged_decode",
    "ln_fwd",
    "ln_bwd",
    "rms_fwd",
    "rms_bwd",
    "adam_flat",
    "sumsq_flat",
)

#: the key under which a kernel's name rides in ``kernel_metadata``
KERNEL_KEY = "kernel"


def step_phase(name: str):
    """``jax.named_scope(name)`` for a registered step phase."""
    if name not in STEP_PHASES:
        raise ValueError(
            f"unknown step phase {name!r}; the registry is closed "
            f"(goodput.scopes.STEP_PHASES): {STEP_PHASES}"
        )
    import jax

    return jax.named_scope(name)


def model_scope(name: str):
    """``jax.named_scope(name)`` for a registered part of the model."""
    if name not in MODEL_SCOPES:
        raise ValueError(
            f"unknown model scope {name!r}; the registry is closed "
            f"(goodput.scopes.MODEL_SCOPES): {MODEL_SCOPES}"
        )
    import jax

    return jax.named_scope(name)


def kernel_metadata(name: str, **tiles: int) -> dict:
    """The ``metadata=`` of a ``pl.pallas_call`` for a registered kernel;
    ``tiles`` (``block_q=512, block_k=512``) ride beside the name, so a
    capture shows which tiles each call of a step ran."""
    if name not in KERNELS:
        raise ValueError(
            f"unknown kernel {name!r}; the registry is closed "
            f"(goodput.scopes.KERNELS): {KERNELS}"
        )
    return {KERNEL_KEY: name, **{k: str(v) for k, v in tiles.items()}}
