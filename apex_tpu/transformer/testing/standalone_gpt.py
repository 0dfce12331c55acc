"""Standalone GPT pretraining driven by the Megatron argument system.

Reference parity: apex/transformer/testing/standalone_gpt.py (the runnable
GPT its pipeline tests launch) on top of standalone_transformer_lm.py. Here
the model stack is apex_tpu.models (Embedding + ParallelTransformer + head)
and the schedule comes from ``get_forward_backward_func`` exactly like the
reference's test driver: no-pipelining for pp=1, the compiled 1F1B /
interleaved scans otherwise.

Run (virtual CPU mesh):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m apex_tpu.transformer.testing.standalone_gpt \
        --num-layers 4 --hidden-size 64 --num-attention-heads 4 \
        --seq-length 32 --max-position-embeddings 32 \
        --micro-batch-size 2 --global-batch-size 8 \
        --pipeline-model-parallel-size 2 --tensor-model-parallel-size 2 \
        --train-iters 3
"""

import functools

import jax
import jax.numpy as jnp
from apex_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt_pipeline import build_gpt_pipeline
from apex_tpu.monitor.xray import ledger as xlax
from apex_tpu.optimizers import fused_adam
from apex_tpu.parallel import parallel_state
from apex_tpu.parallel.ddp import all_reduce_gradients
from apex_tpu.parallel.pipeline import forward_backward_with_pre_post
from apex_tpu.transformer import TransformerConfig
from apex_tpu.transformer.testing import global_vars
from apex_tpu.transformer.testing.arguments import parse_args


def gpt_config_from_args(args) -> TransformerConfig:
    """The reference's gpt_model_provider reads get_args() field by field
    (standalone_gpt.py:33-45); the shared mapping lives in
    arguments.transformer_config_from_args — only the determinism knobs
    differ (the ref tests run dropout-free)."""
    import dataclasses

    from apex_tpu.transformer.testing.arguments import (
        transformer_config_from_args,
    )

    return dataclasses.replace(
        transformer_config_from_args(args),
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )


def _make_router(args):
    """Telemetry sinks from the Megatron argument surface: jsonl via
    ``--metrics-jsonl``, TensorBoard via ``--tensorboard-dir`` (gated on a
    writer being importable), one shared record schema with the other
    producers (apex_tpu.monitor, docs/observability.md). None when no
    sink is requested."""
    from apex_tpu import monitor

    sinks = []
    if getattr(args, "metrics_jsonl", None):
        sinks.append(monitor.JsonlSink(args.metrics_jsonl))
    if getattr(args, "tensorboard_dir", None):
        tb = monitor.try_tensorboard_sink(args.tensorboard_dir)
        if tb is not None:
            sinks.append(tb)
    return monitor.MetricRouter(sinks) if sinks else None


def run_gpt(args=None, log=print):
    """Build mesh + model from args, train ``--train-iters`` steps, return
    the per-step loss list (every loss is the dp/pp-published global mean)."""
    if args is None:
        args = global_vars.get_args()
    tp = args.tensor_model_parallel_size
    pp = args.pipeline_model_parallel_size
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=tp,
        pipeline_model_parallel_size=pp,
        virtual_pipeline_model_parallel_size=(
            args.virtual_pipeline_model_parallel_size
        ),
    )
    dp = parallel_state.get_data_parallel_world_size()
    cfg = gpt_config_from_args(args)

    seq = args.seq_length
    mb = args.micro_batch_size
    num_micro = args.global_batch_size // (mb * dp)
    if num_micro < 1:
        raise ValueError("global batch too small for micro batch x dp")
    if pp > 1 and num_micro % pp != 0:
        # interleaved/1F1B scans want M % P == 0 for the interleaved case;
        # round up like the reference pads its last batch
        num_micro = -(-num_micro // pp) * pp

    parts = build_gpt_pipeline(cfg, pp)
    key = jax.random.PRNGKey(args.seed)
    steps = args.train_iters or 3
    tokens = jax.random.randint(
        key, (steps, num_micro, mb * dp, seq), 0, cfg.vocab_size
    )
    labels = jnp.roll(tokens, -1, axis=3)

    opt = fused_adam(lr=args.lr or 1e-3, betas=(args.adam_beta1, args.adam_beta2),
                     eps=args.adam_eps, weight_decay=args.weight_decay)

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, None, "dp"), P(None, None, "dp")),
        out_specs=P(),
        check_vma=False,
    )
    def train(tokens, labels):
        # muted: this init block runs ONCE PER RUN, not once per step —
        # its collectives (the vocab-parallel embedding's psum, the
        # stage-init forward's RowParallel psums) must not inflate the
        # ledger's per-step comms totals
        with xlax.muted():
            init_key = jax.random.PRNGKey(args.seed)
            pre = parts.embed.init(init_key, tokens[0, 0])["params"]
            h0 = parts.pre_fn(pre, tokens[0, 0])
            r = jax.lax.axis_index("pp")
            stage = parts.chunk.init(
                jax.random.fold_in(jax.random.fold_in(init_key, 7), r), h0
            )["params"]
            params = {
                "pre": pre,
                "stages": stage,
                "post": parts.init_post(jax.random.fold_in(init_key, 9)),
            }
            opt_state = opt.init(params)

        def one_step(carry, batch):
            params, opt_state = carry
            toks, labs = batch
            loss, _, grads = forward_backward_with_pre_post(
                parts.pre_fn, parts.stage_fn, parts.post_loss_fn, params,
                toks, labs, axis_name="pp",
                grad_sync_fn=lambda g: all_reduce_gradients(g, axis_name="dp"),
            )
            updates, opt_state = opt.update(grads, opt_state, params)
            import optax

            params = optax.apply_updates(params, updates)
            # under SP the post loss is tp-local (pre-divided by tp in
            # post_loss_fn) so psum completes the token mean; without SP
            # the loss is already tp-replicated and a psum would scale by tp
            if cfg.sequence_parallel and tp > 1:
                loss = xlax.psum(loss, "tp")
            loss = xlax.pmean(loss, "dp")
            return (params, opt_state), loss

        _, losses = jax.lax.scan(one_step, (params, opt_state), (tokens, labels))
        return losses

    router = _make_router(args)

    # X-ray startup banner (docs/observability.md): static introspection
    # of the compiled run BEFORE it executes — per-step comms volume from
    # a ledger trace (the whole run is one scan over steps, so the traced
    # step body IS one step's traffic; the once-per-run init block is
    # muted), and XLA's memory breakdown (NOTE: one extra compile — on
    # jax 0.4.x the AOT compile does not share the jit dispatch cache,
    # see xray.memory_report). Records join the same jsonl stream as
    # metrics when a sink is configured.
    if getattr(args, "xray_comms", False):
        from apex_tpu.monitor import xray

        led = xray.predict_comms(train, tokens, labels)
        log(led.summary())
        if router is not None:
            for rec in led.to_records(step=0):
                router.emit(rec)
    if getattr(args, "xray_report", False):
        from apex_tpu.monitor import xray

        report = xray.memory_report(train, tokens, labels)
        log(report.format())
        if router is not None:
            router.event("memory", 0, **report.fields())

    import time

    from apex_tpu.utils.timers import step_annotation

    t0 = time.perf_counter()
    # the whole run is ONE compiled scan, so per-step markers are
    # impossible; the single annotation still makes any profiler window
    # over this run segmentable (as one span covering all steps) by the
    # timeline analyzer (apex_tpu.monitor.xray.timeline) instead of
    # marker-less noise
    with step_annotation(0, name="train_scan"):
        losses = jax.device_get(train(tokens, labels))  # ONE fetch, all steps
    elapsed = max(time.perf_counter() - t0, 1e-9)
    for i, l in enumerate(losses):
        log(f"iteration {i:4d} | lm loss {float(l):.4f}")

    if router is not None:
        from apex_tpu import monitor

        interval = max(1, args.log_interval or 1)
        for i, l in enumerate(losses):
            if i % interval == 0 or i == len(losses) - 1:
                router.metrics(i, loss=float(l))
        # the whole run is ONE jitted scan, so per-step device time is not
        # separable here; the throughput record is honest about covering
        # compile + dispatch + all steps
        # num_micro may be rounded UP to a pp multiple above — count the
        # tokens the scan actually processed, not the nominal global batch
        tokens_per_step = num_micro * mb * dp * seq
        sec_per_step = elapsed / max(1, steps)
        router.event(
            "throughput", steps - 1,
            tokens_per_s=monitor.tokens_per_second(
                tokens_per_step * steps, elapsed
            ),
            mfu=monitor.mfu(
                monitor.training_flops_per_step(
                    monitor.gpt_flops_per_token(cfg, seq), tokens_per_step
                ),
                sec_per_step,
                num_devices=len(jax.devices()),
            ),
            wall_s=elapsed,
        )
        router.close()
    parallel_state.destroy_model_parallel()
    return [float(l) for l in losses]


def main(argv=None):
    args = parse_args(args=argv)
    return run_gpt(args)


if __name__ == "__main__":
    main()
