"""Harnesses for BASELINE.json configs 2-5.

BASELINE.md names five configurations to baseline; config 1 (RN50 amp O2)
is the headline `bench.py`. This file makes the other four measurable:

2. ``mlp``   — MLP regression, FusedAdam + multi-tensor l2norm grad clip
              (the examples/simple flow), steps/sec.
3. ``dp``    — ResNet-50 data-parallel + SyncBatchNorm over the mesh
              (ICI on real hardware, the virtual CPU mesh elsewhere),
              global imgs/sec.
4. ``bert``  — BERT fine-tune step, FusedLAMB + fused LayerNorm kernels,
              sequences/sec.
5. ``gpt``   — GPT via the parallel transformer layer, tensor-parallel
              mesh (tp=8 on a pod slice; tp=2 CPU smoke), tokens/sec.
+. ``llama`` — extension: llama-family (RMSNorm/RoPE/SwiGLU/GQA/no-bias)
              training step, tokens/sec.

Each config prints one JSON line {config, metric, value, unit, platform}.
Sizes scale down automatically off-TPU so the harness is runnable (and
CI-checkable) anywhere; every record names the platform it ran on, and a
CPU record is a CPU time, never a device number.

Usage: python benchmarks/bench_configs.py [--cpu] [--configs mlp,dp,...]
"""

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax


def _timed_steps(step, state, batches):
    """Steps/sec via chained-scan slope timing (methodology in
    apex_tpu/utils/benchmarking.py: the slope cancels every per-call
    constant).  The batch is fixed at
    ``batches(0)`` for every chained step, standard for throughput."""
    import numpy as np

    from apex_tpu.utils.benchmarking import chained_seconds_per_iter, full_reduce

    b = batches(0)

    def build(k):
        def run(state, *b):
            def body(c, _):
                return step(c, *b), None

            c, _ = jax.lax.scan(body, state, None, length=k)
            return full_reduce(c)

        return run

    sec, out = chained_seconds_per_iter(
        build, (state, *b), reps=3, target_signal=0.5, max_span=256,
        return_output=True,
    )
    assert np.isfinite(out[0]), f"diverged during timing: state sum={out[0]}"
    return 1.0 / sec


def bench_mlp(tpu):
    """Config 2: amp O2 MLP regression, FusedAdam, l2norm grad clip."""
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.ops import mlp_init, mlp_apply
    from apex_tpu.optimizers import clip_grad_norm, fused_adam

    dims = [1024, 4096, 4096, 1] if tpu else [256, 512, 512, 1]
    batch = 4096 if tpu else 512
    params = mlp_init(jax.random.PRNGKey(0), dims)
    params, amp_opt, policy = amp.initialize(
        params, fused_adam(lr=1e-3), opt_level="O2"
    )
    state = amp_opt.init(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, dims[0]), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(2), (batch, 1), jnp.float32)

    @jax.jit
    def step(carry, x, y):
        params, state = carry

        def scaled(p):
            h = mlp_apply(p, policy.cast_inputs(x))
            return amp_opt.scale_loss(
                jnp.mean((h.astype(jnp.float32) - y) ** 2), state
            )

        grads = jax.grad(scaled)(params)
        grads, _ = clip_grad_norm(grads, 1.0)
        params, state, _ = amp_opt.step(grads, state, params)
        return params, state

    sps = _timed_steps(step, (params, state), lambda i: (x, y))
    return {"config": "mlp_fusedadam_clip", "metric": "steps_per_sec",
            "value": round(sps, 2), "unit": "steps/sec"}


def bench_dp_syncbn(tpu):
    """Config 3: RN50 DP + SyncBatchNorm over the mesh."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from apex_tpu.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.models import cross_entropy_loss
    from apex_tpu.models.resnet import BasicBlock, ResNet
    from apex_tpu.optimizers import fused_sgd

    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    if tpu:
        model = ResNet(stage_sizes=[3, 4, 6, 3], num_classes=1000,
                       dtype=jnp.bfloat16, bn_axes=("dp",))
        per_dev, image = 64, 176
    else:
        model = ResNet(stage_sizes=[1, 1], block_cls=BasicBlock,
                       num_filters=8, num_classes=10, bn_axes=("dp",))
        per_dev, image = 4, 32
    batch = per_dev * n_dev
    key = jax.random.PRNGKey(0)
    images = jax.random.normal(key, (batch, image, image, 3), jnp.float32)
    labels = jax.random.randint(jax.random.fold_in(key, 1), (batch,), 0, 10)
    variables = jax.jit(model.init)(key, images[:2])
    opt = fused_sgd(lr=0.1, momentum=0.9)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=((P(), P(), P()), P("dp"), P("dp")),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def step(carry, images, labels):
        params, bs, opt_state = carry

        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": bs}, images, train=True,
                mutable=["batch_stats"],
            )
            # differentiate the GLOBAL loss: sync BN psums inside forward
            return jax.lax.pmean(
                cross_entropy_loss(logits, labels), "dp"
            ), mut["batch_stats"]

        grads, new_bs = jax.grad(loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_bs, opt_state)

    carry = (variables["params"], variables["batch_stats"],
             opt.init(variables["params"]))
    sps = _timed_steps(step, carry, lambda i: (images, labels))
    return {"config": "rn50_dp_syncbn", "metric": "imgs_per_sec_global",
            "value": round(sps * batch, 2), "unit": "imgs/sec",
            "devices": n_dev}


def bench_bert(tpu):
    """Config 4: BERT fine-tune step, FusedLAMB + fused LayerNorm."""
    import jax.numpy as jnp
    import optax

    from apex_tpu.models.bert import BertModel
    from apex_tpu.optimizers import fused_lamb
    from apex_tpu.transformer import TransformerConfig

    if tpu:
        cfg = TransformerConfig(
            num_layers=12, hidden_size=768, num_attention_heads=12,
            vocab_size=30528, max_position_embeddings=512,
            hidden_dropout=0.0, attention_dropout=0.0,
            compute_dtype=jnp.bfloat16,
        )
        batch, seq = 32, 384
    else:
        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            vocab_size=512, max_position_embeddings=64,
            hidden_dropout=0.0, attention_dropout=0.0,
        )
        batch, seq = 4, 32
    model = BertModel(config=cfg, add_binary_head=False)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.fold_in(key, 1), (batch, seq), 0,
                                cfg.vocab_size)
    params = model.init(key, tokens, lm_labels=labels)["params"]
    opt = fused_lamb(lr=1e-4, weight_decay=0.01)

    @jax.jit
    def step(carry, tokens, labels):
        params, opt_state = carry

        def loss_fn(p):
            lm_loss, _ = model.apply({"params": p}, tokens, lm_labels=labels)
            return jnp.mean(lm_loss)

        grads = jax.grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state)

    sps = _timed_steps(step, (params, opt.init(params)),
                          lambda i: (tokens, labels))
    return {"config": "bert_fusedlamb", "metric": "sequences_per_sec",
            "value": round(sps * batch, 2), "unit": "seq/sec"}


def bench_gpt_tp(tpu, force_tp=None):
    """Config 5: GPT through the parallel transformer layer on a tp mesh.
    ``force_tp`` drives the --sweep-tp scaling table (the reference's
    tests/L0/run_transformer/gpt_scaling_test.py role)."""
    import jax.numpy as jnp
    import optax

    from apex_tpu.models import GPTModel
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.parallel import parallel_state
    from apex_tpu.transformer import TransformerConfig

    from apex_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    n_dev = len(jax.devices())
    tp = force_tp or (8 if (tpu and n_dev >= 8) else min(2, n_dev))
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=jax.devices()[:tp]
    )
    if tpu:
        cfg = TransformerConfig(
            num_layers=24, hidden_size=1024, num_attention_heads=16,
            vocab_size=50304, max_position_embeddings=1024,
            hidden_dropout=0.0, attention_dropout=0.0,
            sequence_parallel=True, compute_dtype=jnp.bfloat16,
        )  # GPT-2 345M
        batch, seq = 8, 1024
    else:
        # smoke shape divides through tp=8 (heads % tp, hidden % (tp*heads))
        cfg = TransformerConfig(
            num_layers=2, hidden_size=128, num_attention_heads=8,
            vocab_size=512, max_position_embeddings=64,
            hidden_dropout=0.0, attention_dropout=0.0,
            sequence_parallel=tp > 1,
        )
        batch, seq = 2, 32
    model = GPTModel(config=cfg)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=False,
    )
    def init_params(tokens, labels):
        return model.init(jax.random.PRNGKey(0), tokens, labels=labels)["params"]

    params = jax.jit(init_params)(tokens, labels)
    opt = fused_adam(lr=1e-4)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=((P(), P()), P(), P()),
        out_specs=(P(), P()), check_vma=False,
    )
    def step(carry, tokens, labels):
        params, opt_state = carry

        def loss_fn(p):
            losses = model.apply({"params": p}, tokens, labels=labels)
            return jnp.mean(losses)

        grads = jax.grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state)

    sps = _timed_steps(step, (params, opt.init(params)),
                          lambda i: (tokens, labels))
    parallel_state.destroy_model_parallel()
    return {"config": "gpt_tensor_parallel", "metric": "tokens_per_sec",
            "value": round(sps * batch * seq, 2), "unit": "tokens/sec",
            "tp": tp}


def bench_llama(tpu):
    """Extension config (beyond BASELINE 1-5): llama-family training step —
    RMSNorm + rotate-half RoPE + SwiGLU + GQA + bias-free linears, the
    modern-architecture path the GQA/flash kernels exist for."""
    import jax.numpy as jnp
    import optax

    from apex_tpu.models import GPTModel
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.transformer import TransformerConfig

    common = dict(
        hidden_dropout=0.0, attention_dropout=0.0,
        normalization="rmsnorm", activation="swiglu",
        add_bias_linear=False, position_embedding_type="rope",
        share_embeddings_and_output_weights=False,
    )
    if tpu:
        cfg = TransformerConfig(
            num_layers=16, hidden_size=1024, num_attention_heads=16,
            num_query_groups=4, ffn_hidden_size=2816, vocab_size=32000,
            max_position_embeddings=1024, compute_dtype=jnp.bfloat16,
            **common,
        )  # ~llama-ish 250M
        batch, seq = 8, 1024
    else:
        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            num_query_groups=2, ffn_hidden_size=160, vocab_size=512,
            max_position_embeddings=64, compute_dtype=jnp.float32,
            **common,
        )
        batch, seq = 2, 32
    model = GPTModel(config=cfg)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    params = jax.jit(model.init)(key, tokens, labels=labels)
    opt = fused_adam(lr=1e-4)

    def step(carry, tokens, labels):
        params, opt_state = carry

        def loss_fn(p):
            return jnp.mean(model.apply(p, tokens, labels=labels))

        grads = jax.grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state)

    sps = _timed_steps(step, (params, opt.init(params)),
                       lambda i: (tokens, labels))
    return {"config": "llama_gqa", "metric": "tokens_per_sec",
            "value": round(sps * batch * seq, 2), "unit": "tokens/sec"}


def bench_decode(tpu):
    """KV-cache decode throughput (extension config; the reference has no
    inference path). Tokens/sec of greedy generation on the llama-flavored
    stack, slope-timed between two generation lengths so prefill and every
    per-call constant cancel (same methodology as the training rows)."""
    import jax.numpy as jnp

    from apex_tpu.models import GPTModel
    from apex_tpu.models.generate import generate
    from apex_tpu.transformer import TransformerConfig
    from apex_tpu.utils.benchmarking import (
        chained_seconds_per_iter,
        full_reduce,
    )

    common = dict(
        hidden_dropout=0.0, attention_dropout=0.0,
        normalization="rmsnorm", activation="swiglu",
        add_bias_linear=False, position_embedding_type="rope",
        share_embeddings_and_output_weights=False,
    )
    if tpu:
        cfg = TransformerConfig(
            num_layers=16, hidden_size=1024, num_attention_heads=16,
            num_query_groups=4, ffn_hidden_size=2816, vocab_size=32000,
            max_position_embeddings=2048, compute_dtype=jnp.bfloat16,
            **common,
        )
        batch, prompt_len = 8, 128
    else:
        cfg = TransformerConfig(
            num_layers=2, hidden_size=64, num_attention_heads=4,
            num_query_groups=2, ffn_hidden_size=160, vocab_size=512,
            # covers prompt + the span escalation's largest chain (257)
            max_position_embeddings=512, compute_dtype=jnp.float32,
            **common,
        )
        batch, prompt_len = 2, 16
    model = GPTModel(config=cfg)
    key = jax.random.PRNGKey(0)
    prompt = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    variables = jax.jit(model.init)(key, prompt)

    def build(k):
        def run(variables, prompt):
            out = generate(model, variables, prompt, max_new_tokens=k)
            return full_reduce(out)

        return run

    sec_per_tok = chained_seconds_per_iter(
        build, (variables, prompt), reps=2, max_span=256
    )
    return {"config": "decode_kv_cache", "metric": "tokens_per_sec",
            "value": round(batch / sec_per_tok, 2), "unit": "tokens/sec"}


CONFIGS = {
    "mlp": bench_mlp,
    "dp": bench_dp_syncbn,
    "bert": bench_bert,
    "gpt": bench_gpt_tp,
    "llama": bench_llama,
    "decode": bench_decode,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--configs", default="mlp,dp,bert,gpt")
    ap.add_argument("--sweep-tp", action="store_true",
                    help="run the gpt config over tp in {1,2,4,8} (clamped "
                         "to device count) — the reference's "
                         "gpt_scaling_test.py sweep as a harness")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    from apex_tpu.ops._dispatch import on_tpu

    tpu = on_tpu()
    if args.sweep_tp:
        n_dev = len(jax.devices())
        for tp in (1, 2, 4, 8):
            if tp > n_dev:
                break
            rec = bench_gpt_tp(tpu, force_tp=tp)
            rec["platform"] = platform
            print(json.dumps(rec))
        return
    for name in args.configs.split(","):
        rec = CONFIGS[name](tpu)
        rec["platform"] = platform
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
