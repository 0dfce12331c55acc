"""apex_tpu headline benchmark.

Metric (BASELINE.md): ImageNet ResNet-50 imgs/sec/chip under amp O2.
The reference publishes no absolute numbers (BASELINE.json published: {}),
so ``vs_baseline`` is the O2 speedup over the O0 (fp32, no amp) step on the
same chip — the reference's own L1 methodology (O-level cross-product vs an
O0 baseline, /root/reference/tests/L1/common/run_test.sh:20-49) turned into
a throughput ratio.

One process, on the chip: prints exactly ONE JSON line
  {"metric": ..., "value": N, "unit": "imgs/sec/chip", "vs_baseline": N,
   "device": {...}}
and exits non-zero, printing no record, when there is no TPU or when either
measurement fails. There is no fallback: a CPU run is not this metric.
"""

import json
import sys


def measure(dtype, batch, image_size):
    """ResNet-50 images/sec for one train step (loss + grads + optimizer
    update), slope-timed: the step is chained k times inside one jitted
    ``lax.scan`` and the per-step time is the slope between two chain
    lengths, which cancels every per-call constant
    (apex_tpu/utils/benchmarking.py)."""
    import jax
    import jax.numpy as jnp
    import optax

    from apex_tpu.models import ResNet50, cross_entropy_loss
    from apex_tpu.optimizers import fused_sgd
    from apex_tpu.utils.benchmarking import chained_seconds_per_iter, full_reduce

    model = ResNet50(num_classes=1000, dtype=dtype)
    key = jax.random.PRNGKey(0)
    # images/labels are jit arguments, not closure constants — closed-over
    # arrays would be baked into the HLO as a ~150 MB constant at batch 256
    images = jax.random.normal(key, (batch, image_size, image_size, 3), jnp.float32)
    labels = jax.random.randint(jax.random.fold_in(key, 1), (batch,), 0, 1000)

    variables = jax.jit(model.init)(key, images)
    params, batch_stats = variables["params"], variables["batch_stats"]
    # examples/imagenet/main_amp.py trains RN50 with momentum SGD
    opt = fused_sgd(lr=0.1, momentum=0.9, weight_decay=1e-4)
    opt_state = opt.init(params)

    def build(k):
        def run(params, batch_stats, opt_state, images, labels):
            def loss_fn(p, bstats):
                logits, mutated = model.apply(
                    {"params": p, "batch_stats": bstats},
                    images,
                    train=True,
                    mutable=["batch_stats"],
                )
                return cross_entropy_loss(logits, labels), mutated["batch_stats"]

            def body(carry, _):
                params, batch_stats, opt_state = carry
                (loss, bs), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch_stats)
                updates, opt_state2 = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, bs, opt_state2), loss

            (params, batch_stats, opt_state), losses = jax.lax.scan(
                body, (params, batch_stats, opt_state), None, length=k
            )
            # full param reduction keeps every update lane live
            # (elementwise chains are otherwise DCE-narrowed to the
            # fetched element)
            return losses[-1], full_reduce(params)

        return run

    # raises on a non-positive slope rather than emitting garbage
    # throughput; every span escalation is another full RN50-scan compile,
    # and span 32 already gives multiple seconds of signal at batch 256
    sec_per_step, (loss, norm) = chained_seconds_per_iter(
        build, (params, batch_stats, opt_state, images, labels),
        reps=2, target_signal=0.4, max_span=64, return_output=True,
    )
    # correctness gate on the (already-fetched) timed outputs
    if not (jnp.isfinite(loss) and jnp.isfinite(norm)):
        raise FloatingPointError(
            f"diverged: loss={loss} param_norm_sq={norm}")
    return batch / sec_per_step


def main():
    """O2 then O0 on the chip, in this one process (a chip belongs to one
    process at a time). Fails — non-zero exit, no record — without a TPU
    or when either measurement raises: a number from anywhere else is not
    this metric."""
    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from apex_tpu.ops._dispatch import on_tpu

    dev = jax.devices()[0]  # backend init: raises here, not mid-bench
    if not on_tpu():
        sys.stderr.write(
            f"[bench] no TPU (platform {dev.platform!r}): the RN50 metric "
            "is a device number and is not measured off the device\n")
        return 1

    batch, image_size = 256, 224
    o2 = measure(jnp.bfloat16, batch, image_size)  # amp O2
    o0 = measure(jnp.float32, batch, image_size)   # O0
    print(json.dumps({
        "metric": "rn50_train_imgs_per_sec_per_chip_ampO2",
        "value": round(o2, 2),
        "unit": "imgs/sec/chip",
        "o0_value": round(o0, 2),
        "vs_baseline": round(o2 / o0, 3),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
