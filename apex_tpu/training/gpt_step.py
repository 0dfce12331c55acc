"""The GPT training step and its signature: the one way to build it.

:func:`build_gpt_training` turns a :class:`GPTTargetConfig` into one jitted,
shard-mapped, donating ``train_step`` and the recipes that initialise its
state (:class:`GPTTraining`). Everything that trains a GPT here goes through
it: the benchmark's cells (``perf/drivers/``), ``chip_smoke.py``,
``examples/gpt/pretrain_gpt.py``, the replayer and the remediation campaign.

What is in the step: the bf16 TP/SP model (GPT-2-shaped from the config's five
integers, or whatever ``GPTTargetConfig.model`` describes: latent attention,
expert layers, a multi-token-prediction block), fused Adam or ZeRO-2
``DistributedFusedAdam`` (``zero=True``), optional int8/fp8 compressed dp
gradient sync with the error-feedback residual riding the opt-state slot,
dynamic loss scaling with the dp-consensus ``found_inf`` under ZeRO, the
anomaly sentinel's gate through ``vma_cond``, the chaos ``poison_loss`` arm,
the escalation policy's ``lr_scale`` input, and the on-device MetricBag taps.

The step's signature (``collect_layer_rms`` appends ``layer_rms``, the
per-layer ``layer_out_rms`` taps of monitor/taps.py as a ``(layers,)`` fp32
vector; ``collect_expert_choices`` the experts every token chose)::

    (params, opt_state, scaler_state, sent_state, bag,
     tokens, labels, inject_nan, lr_scale)
      -> (params, opt_state, scaler_state, sent_state, bag,
          loss, verdict[, layer_rms][, expert_choices])

The replay contract: bit-exact replay only works when recorder and replayer
execute the SAME compiled computation, so the flight recorder journals
``GPTTargetConfig.to_json()`` in its header and the replayer
(``resilience/replay/replayer.py``) rebuilds the step from that header through
this builder. A field added here gets a default that reproduces the step
older journals recorded.

``build_gpt_training`` initializes ``parallel_state`` (process-global, the
example/CLI convention). The donating jit constructed here is an AUDITED
entrypoint (allowlist ``lint.jit-donate`` entry; the GPT example verifies it
with ``--audit-donation``).
"""

import dataclasses
import functools
import re
from typing import Any, Optional, Tuple

import numpy as np

__all__ = [
    "GPTTargetConfig",
    "GPTTraining",
    "build_gpt_training",
]


@dataclasses.dataclass(frozen=True)
class GPTTargetConfig:
    """Everything the compiled GPT step depends on — the journal-header
    replay recipe. Field defaults mirror the example's CLI defaults."""

    vocab: int = 512
    seq_len: int = 128
    layers: int = 4
    hidden: int = 256
    heads: int = 8
    tp: int = 1
    sequence_parallel: bool = True
    micro_batch: int = 4
    global_batch: int = 16
    lr: float = 3e-4
    weight_decay: float = 0.01
    seed: int = 0
    zero: bool = False
    compression: str = "none"
    compression_block: int = 128
    spike_z: float = 6.0
    spike_warmup: int = 10
    skip_budget: int = 1
    rollback_budget: int = 2
    collect_layer_rms: bool = False
    #: append the experts every token chose in this step to the step's
    #: outputs, after ``layer_rms`` where that is on: int32 (dp, microbatches,
    #: expert layers, tokens, top_k), trunk layers in depth order, then the
    #: multi-token-prediction block's. What ran is what is read: an output
    #: check compares them with a reference's choices (PERF.md 2)
    collect_expert_choices: bool = False
    #: cap the mesh to the first N visible devices (None = all). The
    #: in-process topology changes of the remediation selftest/campaign
    #: build an 8-device and a 4-device training in ONE process (the
    #: elastic-selftest sub-mesh trick, through parallel_state's
    #: ``devices=``); cross-process runs keep None and size the world
    #: with XLA_FLAGS instead.
    max_devices: Optional[int] = None
    #: the model's description beyond (layers, hidden, heads, vocab,
    #: seq_len): ``TransformerConfig`` fields as sorted (name, value)
    #: pairs (``apex_tpu.models.arch`` makes them from an architecture
    #: file and the share this program holds). None = the GPT-2-shaped
    #: model the five integers describe. A dict in JSON.
    model: Optional[Tuple[Tuple[str, Any], ...]] = None

    def __post_init__(self):
        if self.model is not None:
            object.__setattr__(self, "model", _pairs(self.model))

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        if self.model is not None:
            d["model"] = {k: list(v) if isinstance(v, tuple) else v
                          for k, v in self.model}
        return d

    @classmethod
    def from_json(cls, d: dict) -> "GPTTargetConfig":
        """Tolerant of extra keys (an older replayer reading a newer
        journal must fail on MISSING semantics, not added ones)."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


def _pairs(model) -> Tuple[Tuple[str, Any], ...]:
    """A model description (dict, or pairs) as sorted hashable pairs."""
    items = model.items() if isinstance(model, dict) else model
    return tuple(sorted(
        (str(k), tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in items))


@dataclasses.dataclass
class GPTTraining:
    """The built pieces :func:`build_gpt_training` returns."""

    cfg: GPTTargetConfig
    mesh: Any
    dp: int
    num_micro: int
    model: Any
    transformer_config: Any
    opt: Any
    opt_specs: Any
    scaler: Any
    sentinel: Any
    train_step: Any          # jitted + shard_mapped, donate_argnums (0..3)
    metric_spec: dict
    replicated: Any          # NamedSharding(mesh, P())
    ddp_compressed: bool

    def init_state(self) -> Tuple[Any, Any, Any, Any]:
        """(params, opt_state, scaler_state, sent_state) — the donated
        carried state, sharded exactly as the step expects (the example's
        init block, verbatim)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from apex_tpu.compat import shard_map

        cfg = self.cfg
        sample_tokens = jnp.zeros((cfg.micro_batch, cfg.seq_len), jnp.int32)

        # tp-sharded init must run under the mesh like the step — and
        # jitted: an un-jitted shard_map executes primitive by primitive
        # across the mesh (measured: a minute for a 2-layer model on 8
        # virtual devices, against seconds as one program)
        @jax.jit
        @functools.partial(
            shard_map, mesh=self.mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def init_params(tokens):
            # a multi-token-prediction block is only traced with labels
            labels = ({"labels": tokens}
                      if self.transformer_config.mtp_num_layers else {})
            return self.model.init(
                jax.random.PRNGKey(cfg.seed), tokens, **labels)

        params = init_params(sample_tokens)
        # optimizer/scaler state is pinned to the SAME mesh-replicated
        # sharding as the params: plain jit would leave its scalar leaves
        # committed to device 0, which breaks the moment the state
        # round-trips through a checkpoint (restored arrays are
        # committed, and mixed device sets are a hard error)
        if cfg.zero:
            # ZeRO init needs the mesh axis (axis_index slices this
            # rank's shard); the state leaves come out dp-sharded
            # NamedShardings — the elastic restore's target layout
            init_opt = jax.jit(functools.partial(
                shard_map, mesh=self.mesh, in_specs=(P(),),
                out_specs=self.opt_specs, check_vma=False,
            )(self.opt.init))
            opt_state = init_opt(params)
        else:
            opt_state = jax.jit(
                self.opt.init, out_shardings=self.replicated
            )(params)
            if self.ddp_compressed:
                # zero EF residuals, one per rank per param leaf (leading
                # dp dim, dp-sharded — the opt_specs slot layout)
                ef0 = jax.tree_util.tree_map(
                    lambda p: jax.device_put(
                        np.zeros((self.dp,) + tuple(p.shape), np.float32),
                        jax.sharding.NamedSharding(self.mesh, P("dp")),
                    ),
                    params,
                )
                opt_state = {"opt": opt_state, "ef_residual": ef0}
        scaler_state = jax.device_put(self.scaler.init(), self.replicated)
        sent_state = jax.device_put(self.sentinel.init(), self.replicated)
        return params, opt_state, scaler_state, sent_state

    def init_bag(self):
        """A fresh replicated on-device MetricBag."""
        import jax

        from apex_tpu import monitor

        return jax.device_put(
            monitor.metric_bag(self.metric_spec), self.replicated
        )

    def batch_struct(self):
        """ShapeDtypeStruct of the (num_micro, micro*dp, seq) token/label
        arrays the step consumes."""
        import jax
        import jax.numpy as jnp

        return jax.ShapeDtypeStruct(
            (self.num_micro, self.cfg.micro_batch * self.dp,
             self.cfg.seq_len), jnp.int32,
        )

    def reshape_batch(self, x, y):
        """Host (global_batch, seq) arrays -> the step's microbatch
        layout."""
        shape = (self.num_micro, self.cfg.micro_batch * self.dp,
                 self.cfg.seq_len)
        return x.reshape(shape), y.reshape(shape)


def build_gpt_training(cfg: GPTTargetConfig) -> GPTTraining:
    """Build the GPT training step (module docstring).

    Initializes ``parallel_state`` for ``cfg.tp`` (process-global, like
    the example always did) and validates the batch geometry with the
    example's exact error messages.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu import monitor, resilience
    from apex_tpu.amp import GradScaler
    from apex_tpu.compat import shard_map
    from apex_tpu.models import GPTModel, gpt_loss_fn, gpt_mtp_loss_fn
    from apex_tpu.monitor.goodput.scopes import step_phase
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.parallel import parallel_state
    from apex_tpu.parallel.ddp import all_reduce_gradients
    from apex_tpu.parallel.utils import vma_cond
    from apex_tpu.resilience import chaos
    from apex_tpu.transformer import TransformerConfig, calc_params_l2_norm
    from apex_tpu.utils.pytree import tree_any_non_finite

    import optax

    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=cfg.tp,
        devices=(None if cfg.max_devices is None
                 else jax.devices()[: cfg.max_devices]),
    )
    dp = parallel_state.get_data_parallel_world_size()
    num_micro = cfg.global_batch // (cfg.micro_batch * dp)
    assert num_micro >= 1, "global batch too small for micro batch x dp"
    assert cfg.global_batch % (cfg.micro_batch * dp) == 0, (
        f"global batch {cfg.global_batch} must divide evenly into "
        f"micro_batch ({cfg.micro_batch}) x dp ({dp}) microbatches"
    )

    tcfg = TransformerConfig(**dict(
        dict(
            num_layers=cfg.layers,
            hidden_size=cfg.hidden,
            num_attention_heads=cfg.heads,
            vocab_size=cfg.vocab,
            max_position_embeddings=cfg.seq_len,
            hidden_dropout=0.0,
            attention_dropout=0.0,
            sequence_parallel=cfg.sequence_parallel and cfg.tp > 1,
            compute_dtype=jnp.bfloat16,
            collect_layer_metrics=cfg.collect_layer_rms,
        ),
        **dict(cfg.model or ()),
    ))
    model = GPTModel(config=tcfg)
    # expert layers bring per-layer load counters and a router bias that no
    # update may move; a multi-token-prediction block a second loss term
    has_experts = "experts" in {
        tcfg.layer_kinds(i)[1] for i in range(cfg.layers + 1)}
    # the experts each token chose leave the forward pass when the step
    # hands them out or moves the router's bias by them
    keep_choices = has_experts and (
        cfg.collect_expert_choices or tcfg.moe_bias_update_speed > 0)
    n_taps = cfg.layers + tcfg.mtp_num_layers

    # --zero: the ZeRO-2 optimizer's psum_scatter IS the dp gradient sync
    # (average_grads=True completes the mean), so the explicit dp
    # all-reduce below is skipped; its state crosses the shard_map
    # boundary dp-SHARDED (zero_state_specs) and the elastic restore
    # regroups it across a dp-size change (docs/resilience.md)
    # compression: the dp gradient sync travels block-scaled int8/fp8
    # (parallel/compress.py). Under ZeRO the optimizer owns the
    # compressed reduce-scatter AND its error-feedback residual (a state
    # field); under plain DDP the residual rides in the opt_state SLOT as
    # {"opt", "ef_residual"} so every checkpoint/rollback/restore site
    # carries it opaquely
    compress_cfg = None
    if cfg.compression != "none":
        from apex_tpu.parallel.compress import CompressionConfig

        compress_cfg = CompressionConfig(
            dtype=cfg.compression, block_size=cfg.compression_block
        )
    ddp_compressed = compress_cfg is not None and not cfg.zero
    if cfg.zero:
        from apex_tpu.optimizers import (
            distributed_fused_adam, zero_state_specs,
        )

        opt = distributed_fused_adam(
            lr=cfg.lr, weight_decay=cfg.weight_decay, axis_name="dp",
            axis_size=dp, average_grads=True, compression=compress_cfg,
        )
        opt_specs = zero_state_specs("dp", compression=compress_cfg)
    else:
        opt = fused_adam(lr=cfg.lr, weight_decay=cfg.weight_decay)
        # per-rank EF residuals cross the boundary with a leading dp dim
        opt_specs = ({"opt": P(), "ef_residual": P("dp")}
                     if ddp_compressed else P())
    # under ZeRO the grads stay per-rank partials until the optimizer's
    # reduce-scatter, so the overflow flag must join the dp consensus too
    # (without it one rank could skip while the others step)
    scaler = GradScaler(
        loss_scale="dynamic",
        model_parallel_axes=("tp", "pp", "dp") if cfg.zero else ("tp", "pp"),
    )
    sentinel = resilience.AnomalySentinel(
        z_threshold=cfg.spike_z,
        warmup_steps=cfg.spike_warmup,
        skip_budget=cfg.skip_budget,
        rollback_budget=cfg.rollback_budget,
    )

    # tp-replicated params (counted once in the tp-aware grad norm, not
    # per rank): norms, position table, and row-parallel biases — the
    # Megatron tensor_model_parallel-attribute convention
    def tp_duplicated(path):
        return ("layernorm" in path or "position_embeddings" in path
                or path.endswith("dense/bias")
                or path.endswith("dense_4h_to_h/bias"))

    # in-step metric taps: every scalar the host wants to SEE (as opposed
    # to branch on) accumulates on device and crosses once per interval
    metric_spec = {
        "loss": "mean",          # unscaled, dp-averaged
        "grad_norm": "mean",     # global L2 of the unscaled grads
        "loss_scale": "last",    # dynamic-scaler gauge
        "loss_z": "last",        # sentinel z-score of this loss
        "skipped": "sum",        # updates suppressed this interval
        "anomalies": "last",     # sentinel's running total this run
    }
    if tcfg.mtp_num_layers:
        metric_spec.update(
            loss_main="mean",    # next-token cross entropy alone
            loss_mtp="mean",     # the multi-token-prediction term, unweighted
        )
    if has_experts:
        metric_spec.update(
            moe_rows_here="mean",       # assignments on held experts a step
            moe_load_max="max",         # most rows one held expert took
            moe_load_mean="mean",       # rows a held expert took, mean
            moe_load_max_over_mean="mean",  # max / mean a layer, over layers
            moe_dropped="sum",          # assignments the capacity rule cut
            moe_compact_share="mean",   # calls that took the short buffer
        )

    out_specs = (P(), opt_specs, P(), P(), P(), P(), P())
    if cfg.collect_layer_rms:
        out_specs = out_specs + (P(),)
    if cfg.collect_expert_choices:
        out_specs = out_specs + (P("dp"),)

    # donated carried state: params/opt/scaler/sentinel buffers are reused
    # in place across the Python step loop instead of double-buffering the
    # full parameter set in HBM. The metric bag is deliberately NOT
    # donated: its leaves are a handful of scalars, and donating
    # host-rebuilt interval resets risks buffer aliasing across leaves
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), opt_specs, P(), P(), P(), P(None, "dp"),
                  P(None, "dp"), P(), P()),
        out_specs=out_specs,
        check_vma=False,
    )
    def train_step(params, opt_state, scaler_state, sent_state, bag, tokens,
                   labels, inject_nan, lr_scale):
        # every op of the step is traced under one registered phase
        # (goodput.scopes.STEP_PHASES): a profiler capture then says whose
        # each device op is (monitor/xray/timeline scope_map)
        if ddp_compressed:
            # unpack the slot: adam state + this rank's EF residuals
            # (leading dp dim sliced off by shard_map's in_specs)
            with step_phase("grad_sync"):
                ef = jax.tree_util.tree_map(
                    lambda e: e[0], opt_state["ef_residual"]
                )
            opt_state = opt_state["opt"]

        # tokens: (num_micro, micro*dp, seq) -> this dp shard's microbatches
        def micro_loss(p, tok, lab):
            """One microbatch: (loss, taps), taps = {"rms": per-layer
            activation RMS in depth order (monitor/taps.py layer_out_rms, the
            divergence bisector's localization signal), "terms": (main, mtp)
            losses, "moe": per-layer load statistics, "chosen": {expert
            layer's module path: (tokens, top_k)}}, each None where the model
            or the config has nothing to give. The sown values are read via
            mutable intermediates; the forward math does not change by it."""
            out, inter = model.apply(
                p, tok, labels=lab, mutable=["intermediates"])
            inter = inter.get("intermediates", {})
            terms = None
            if tcfg.mtp_num_layers:
                loss, main, mtp = gpt_mtp_loss_fn(*out, tcfg.mtp_loss_coeff)
                terms = jnp.stack([main, mtp])
            else:
                loss = gpt_loss_fn(out)
            return loss, {"rms": _layer_rms_vector(inter, n_taps)
                          if cfg.collect_layer_rms else None,
                          "terms": terms,
                          "moe": _moe_load_stats(inter)
                          if has_experts else None,
                          "chosen": dict(_sown(inter, "moe_chosen"))
                          if keep_choices else None}

        def scaled_total(p):
            if has_experts:
                # one microbatch after another, not vmapped: the experts'
                # grouped matmul takes its group sizes as scalars
                losses, taps = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs),
                    *[micro_loss(p, tokens[i], labels[i])
                      for i in range(num_micro)])
            else:
                losses, taps = jax.vmap(
                    lambda t, l: micro_loss(p, t, l)
                )(tokens, labels)
            rms = taps.pop("rms")
            # multiplicative NaN poison (chaos harness): both the loss and
            # every grad through it go non-finite, like a real blowup
            scaled = chaos.poison_loss(
                scaler.scale(scaler_state, jnp.mean(losses)), inject_nan
            )
            # carry MEAN-OF-SQUARES per layer (shape (layers,)): the sown
            # rms is shard-local (this rank's dp batch slice, and under
            # SP this rank's sequence slice), and equal-size shards mean
            # the global mean-of-squares is just the pmean of the local
            # ones — the sqrt happens after the cross-rank reduction
            if rms is not None:
                rms = jnp.mean(jnp.square(rms.astype(jnp.float32)), axis=0)
            # the other taps ride out beside the RMS, stopped: they are
            # read, never differentiated
            return scaled, (rms, jax.lax.stop_gradient(taps))

        # comms-ledger weighting: collectives inside the vmapped model
        # (fwd AND the custom_vjp bwds) trace with per-MICROBATCH avals
        # while the batched collective ships num_micro x the bytes
        with monitor.xray.scaled(num_micro), step_phase("forward_backward"):
            (loss, (layer_rms, taps)), grads = jax.value_and_grad(
                scaled_total, has_aux=True
            )(params)
        if layer_rms is not None:
            # global per-layer RMS: mean-of-squares pmean'ed over both
            # mesh axes (the out_specs claim P() replication, which the
            # shard-local tap values would silently violate), then sqrt.
            # Size-1 axes elide to nothing; ledger-routed so the comms
            # prediction and the hlo differ both see the (tiny) traffic.
            with step_phase("guard"):
                layer_rms = jnp.sqrt(
                    monitor.xray.ledger.pmean(
                        monitor.xray.ledger.pmean(layer_rms, "tp"), "dp"
                    )
                )
        bias_steps = {}
        if has_experts and tcfg.moe_bias_update_speed > 0:
            with step_phase("guard"):
                bias_steps = _router_bias_steps(
                    taps["chosen"], tcfg.num_moe_experts,
                    tcfg.moe_bias_update_speed, cfg.micro_batch,
                    monitor.xray.ledger.psum)
        new_ef = None
        if not cfg.zero:
            # ZeRO's reduce-scatter inside opt.update replaces this
            # all-reduce (feeding it pre-averaged grads would double-count)
            with step_phase("grad_sync"):
                if ddp_compressed:
                    # error-compensated quantized all-reduce: grads travel
                    # int8 + scales; non-finite grads poison the scales
                    # and still reach found_inf below (the exact
                    # consensus path)
                    grads, new_ef = all_reduce_gradients(
                        grads, axis_name="dp", compression=compress_cfg,
                        ef_state=ef,
                    )
                else:
                    grads = all_reduce_gradients(grads, axis_name="dp")
        with step_phase("unscale"):
            grads, found_inf = scaler.unscale(scaler_state, grads)
            # the scaler's dynamic schedule reacts to true overflow only;
            # the sentinel's spike gate must NOT halve the scale (a spike
            # is not a precision problem)
            new_scaler_state = scaler.update(scaler_state, found_inf)

        with step_phase("guard"):
            # the loss is tp-replicated even under SP: model.apply gathers
            # the sequence before the head and
            # vocab_parallel_cross_entropy psums over tp internally — only
            # the dp average is needed
            unscaled = monitor.xray.ledger.pmean(
                loss / scaler_state.scale, "dp"
            )
            gate = jnp.logical_or(
                found_inf, sentinel.is_anomalous_loss(sent_state, unscaled)
            )

        # the skip must gate the OPTIMIZER STATE too: opt.update on inf
        # grads would fold inf into the Adam moments permanently, nan-ing
        # every later step even after the scaler backs off — same
        # both-or-neither rule as AmpOptimizer.step
        def apply():
            updates, new_opt = opt.update(grads, opt_state, params)
            # rollback escalation dampens the effective LR through here
            updates = jax.tree_util.tree_map(lambda u: u * lr_scale, updates)
            if has_experts:
                # the router's bias is no trained weight: it takes no
                # gradient (moe.router_sigmoid) and nothing of Adam's, decay
                # included; it moves by the balancing rule alone, or not
                updates = jax.tree_util.tree_map_with_path(
                    lambda path, u: bias_steps.get(
                        "/".join(p.key for p in path[1:-1]),
                        jnp.zeros_like(u))
                    if getattr(path[-1], "key", None) == "router_bias"
                    else u, updates)
            return optax.apply_updates(params, updates), new_opt

        with step_phase("optimizer"):
            new_params, new_opt_state = vma_cond(
                gate, lambda: (params, opt_state), apply
            )
        if ddp_compressed:
            # the residual updates even on gated steps (poisoned leaves
            # RESET inside ef_update, so a skipped step cannot freeze a
            # NaN residual); re-pack with the leading dp dim restored
            with step_phase("grad_sync"):
                new_opt_state = {
                    "opt": new_opt_state,
                    "ef_residual": jax.tree_util.tree_map(
                        lambda e: e[None], new_ef
                    ),
                }
        with step_phase("guard"):
            new_sent_state, verdict = sentinel.update(
                sent_state, unscaled, anomaly=gate,
                bad_params=tree_any_non_finite(new_params),
            )
            # metric taps: cheap scalars folded into the on-device bag; the
            # z-score reuses the sentinel's pre-update EMA/var, so the record
            # shows exactly the statistic the verdict was computed from
            new_bag = bag.add(
                loss=unscaled,
                # tp-AWARE global norm: grads of tp-sharded weights are local
                # shards inside shard_map, so the partial sums psum over tp
                # (replicated params counted on rank 0 only); a plain
                # global_grad_norm here would report one shard's norm
                grad_norm=calc_params_l2_norm(
                    grads, tp_duplicate_predicate=tp_duplicated, axis_name="tp"
                ),
                loss_scale=new_scaler_state.scale,
                loss_z=jnp.where(
                    sent_state.count > 0,  # cold-start var=0 makes z garbage
                    (unscaled - sent_state.ema)
                    * jax.lax.rsqrt(sent_state.var + 1e-12),
                    0.0,
                ),
                skipped=jnp.asarray(gate, jnp.float32),
                anomalies=jnp.asarray(new_sent_state.anomalies, jnp.float32),
                **_tap_metrics(taps, monitor.xray.ledger.pmean),
            )
        out = (new_params, new_opt_state, new_scaler_state, new_sent_state,
               new_bag, unscaled, verdict)
        if cfg.collect_layer_rms:
            out = out + (layer_rms,)
        if cfg.collect_expert_choices:
            chosen = taps["chosen"]  # a dict comes back in its keys' order
            out = out + (jnp.stack(
                [chosen[p] for p in sorted(chosen, key=_depth_order)],
                axis=1)[None],)
        return out

    return GPTTraining(
        cfg=cfg, mesh=mesh, dp=dp, num_micro=num_micro, model=model,
        transformer_config=tcfg, opt=opt, opt_specs=opt_specs,
        scaler=scaler, sentinel=sentinel, train_step=train_step,
        metric_spec=metric_spec,
        replicated=jax.sharding.NamedSharding(mesh, P()),
        ddp_compressed=ddp_compressed,
    )


def _sown(intermediates, name):
    """[(module path, value)] of every value the model's layers sowed as
    ``name``, trunk layers in depth order (``layer_10`` after ``layer_9``),
    then a multi-token-prediction block's; a path is as in the parameters'
    tree, "transformer/layer_1/mlp"."""
    found = []

    def visit(node, path):
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            if k == name:
                found.extend(("/".join(path), x) for x in (
                    v if isinstance(v, (tuple, list)) else (v,)))
            else:
                visit(v, path + (str(k),))

    visit(intermediates, ())
    return sorted(found, key=lambda kv: _depth_order(kv[0]))


def _depth_order(path: str):
    """Sort key of a module path: natural order of its digits, a multi-
    token-prediction block after the trunk."""
    return (path.startswith("mtp/"),
            [int(t) if t.isdigit() else t for t in re.split(r"(\d+)", path)])


def _router_bias_steps(chosen, num_experts, speed, micro_batch, psum):
    """DeepSeek-V3's balancing without an auxiliary loss (arXiv 2408.15664;
    the ``noaux_tc`` router's bias): after a step, each expert layer's bias
    goes up by ``speed`` for every expert that took fewer assignments than
    the mean over the step's whole batch and down for every one that took
    more. ``chosen``: {module path: (microbatches, tokens, top_k)}, tokens
    (seq, micro_batch) flattened; of a multi-token-prediction block the
    last position, which has no target, is not counted. Returns {module
    path: the bias's step}."""
    import jax.numpy as jnp

    steps = {}
    for path, c in chosen.items():
        if path.startswith("mtp/"):
            c = c[:, :-micro_batch]
        counts = psum(jnp.sum(
            c[..., None] == jnp.arange(num_experts), axis=(0, 1, 2),
            dtype=jnp.float32), "dp")
        steps[path] = speed * jnp.sign(jnp.mean(counts) - counts)
    return steps


def _moe_load_stats(intermediates):
    """The expert layers' sown ``moe_load`` (rows each held expert took),
    ``moe_dropped`` and ``moe_compact`` as (rows here a step, largest load,
    mean load, max / mean averaged over the layers, dropped, the share of
    the layers whose rows went through the short buffer)."""
    import jax.numpy as jnp

    loads = [v for _, v in _sown(intermediates, "moe_load")]
    dropped = [v for _, v in _sown(intermediates, "moe_dropped")]
    compact = [v for _, v in _sown(intermediates, "moe_compact")]
    load = jnp.stack(loads).astype(jnp.float32)       # (layers, held)
    mean = jnp.mean(load, axis=1)
    return jnp.stack([
        jnp.sum(load), jnp.max(load), jnp.mean(load),
        jnp.mean(jnp.max(load, axis=1) / jnp.maximum(mean, 1e-9)),
        jnp.sum(jnp.stack(dropped)).astype(jnp.float32),
        jnp.mean(jnp.stack(compact).astype(jnp.float32))])


def _tap_metrics(taps, pmean):
    """MetricBag entries from the step's taps (stacked over the
    microbatches), dp-averaged like the loss."""
    import jax.numpy as jnp

    out = {}
    if taps["terms"] is not None:
        main, mtp = pmean(jnp.mean(taps["terms"], axis=0), "dp")
        out.update(loss_main=main, loss_mtp=mtp)
    if taps["moe"] is not None:
        m = taps["moe"]
        out.update(
            moe_rows_here=pmean(jnp.sum(m[:, 0]), "dp"),
            moe_load_max=jnp.max(m[:, 1]),
            moe_load_mean=pmean(jnp.mean(m[:, 2]), "dp"),
            moe_load_max_over_mean=pmean(jnp.mean(m[:, 3]), "dp"),
            moe_dropped=pmean(jnp.sum(m[:, 4]), "dp"),
            moe_compact_share=pmean(jnp.mean(m[:, 5]), "dp"),
        )
    return out


def _layer_rms_vector(intermediates, n_layers: int):
    """The per-layer ``layer_out_rms`` sows as a (layers,) vector in depth
    order."""
    import jax.numpy as jnp

    found = _sown(intermediates, "layer_out_rms")
    if len(found) != n_layers:
        raise ValueError(
            f"expected {n_layers} layer_out_rms taps, found {len(found)} "
            f"({[p for p, _ in found]}) — did a layer refactor rename the "
            f"tap registered in monitor/taps.py?"
        )
    return jnp.stack([jnp.asarray(v, jnp.float32) for _, v in found])
