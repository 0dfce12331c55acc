"""L1-style amp convergence traces.

Mirrors the reference's strongest amp evidence — the L1 harness
(/root/reference/tests/L1/common/run_test.sh:20-49) that trains RN50 over
the opt-level x loss-scale x keep-batchnorm-fp32 cross-product and asserts
trace equality (compare.py:36-47: distributed == single, per-iteration) —
on a CPU-sized ResNet stand-in over the virtual device mesh.

Three families of assertion:
1. distributed (dp=2, sync BN) loss trace == single-device trace, the
   reference's True_/False_ file comparison;
2. every amp config's loss/grad-norm trace tracks the O0 (fp32) trace
   within half-precision tolerance — the "amp didn't change convergence"
   regression bar;
3. fp16 loss-scaling invariants: static scales 1.0 vs 128.0 produce the
   same updates; dynamic scaling trains through its own backoffs.

Everything is deterministic (fixed PRNG keys, fixed synthetic batch —
the stand-in for the reference's --deterministic flag).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.compat import shard_map
from apex_tpu.models.resnet import BasicBlock, ResNet, cross_entropy_loss
from apex_tpu.optimizers import clip_grad_norm, fused_adam, fused_sgd

pytestmark = pytest.mark.slow

STEPS = 8
BATCH = 16
IMAGE = 16
CLASSES = 10


def _data():
    k = jax.random.PRNGKey(7)
    images = jax.random.normal(k, (BATCH, IMAGE, IMAGE, 3), jnp.float32)
    labels = jax.random.randint(jax.random.fold_in(k, 1), (BATCH,), 0, CLASSES)
    return images, labels


def _model(half_dtype, dp=False):
    return ResNet(
        stage_sizes=[1, 1],
        block_cls=BasicBlock,
        num_filters=8,
        num_classes=CLASSES,
        dtype=half_dtype if half_dtype is not None else jnp.float32,
        bn_axes=("dp",) if dp else (),
    )


@functools.lru_cache(maxsize=32)
def run_trace(opt_level, half_name=None, loss_scale=None, keep_bn=None,
              fused=False, dp=False, steps=STEPS):
    """Train the stand-in for ``steps`` and return (losses, grad_norms,
    skipped) as numpy arrays — the in-memory analogue of the reference's
    torch.save'd {Iteration, Loss, Speed} trace files."""
    half = {None: None, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[half_name]
    # model compute dtype follows the opt level (O0/O1 fp32 graph, O2/O3 half)
    model_dtype = half if opt_level in ("O2", "O3") else None
    model = _model(model_dtype, dp=dp)
    images, labels = _data()

    variables = model.init(jax.random.PRNGKey(0), images, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    tx = (fused_adam(lr=2e-3, weight_decay=1e-4) if fused
          else fused_sgd(lr=0.05, momentum=0.9))
    overrides = {}
    if loss_scale is not None:
        overrides["loss_scale"] = loss_scale
    if keep_bn is not None:
        overrides["keep_batchnorm_fp32"] = keep_bn
    params, amp_opt, policy = amp.initialize(
        params, tx, opt_level=opt_level,
        half_dtype=half or jnp.bfloat16, **overrides,
    )
    state = amp_opt.init(params)

    def loss_fn(p, bs, im, lb):
        logits, mut = policy.wrap_apply(model.apply)(
            {"params": p, "batch_stats": bs}, im, train=True,
            mutable=["batch_stats"],
        )
        return cross_entropy_loss(logits, lb), mut["batch_stats"]

    def step(params, bs, state, im, lb):
        def scaled(p):
            loss, new_bs = loss_fn(p, bs, im, lb)
            if dp:
                # differentiate the GLOBAL loss: sync BN's psum creates
                # cross-shard gradient terms, so grad-then-pmean of the
                # local loss is wrong — pmean must sit inside the vjp
                loss = jax.lax.pmean(loss, "dp")
            return amp_opt.scale_loss(loss, state), (loss, new_bs)

        grads, (loss, new_bs) = jax.grad(scaled, has_aux=True)(params)
        _, gnorm_scaled = clip_grad_norm(grads, 1e9)
        gnorm = gnorm_scaled / state.scaler.scale
        params, state, info = amp_opt.step(grads, state, params)
        return params, new_bs, state, loss, gnorm, info["found_inf"]

    if dp:
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        sharded = jax.jit(
            shard_map(
                step, mesh=mesh,
                in_specs=(P(), P(), P(), P("dp"), P("dp")),
                out_specs=(P(), P(), P(), P(), P(), P()),
            )
        )
        step_fn = sharded
    else:
        step_fn = jax.jit(step)

    losses, gnorms, skipped = [], [], []
    for _ in range(steps):
        params, batch_stats, state, loss, gnorm, inf = step_fn(
            params, batch_stats, state, images, labels
        )
        losses.append(float(loss))
        gnorms.append(float(gnorm))
        skipped.append(bool(inf))
    return np.array(losses), np.array(gnorms), np.array(skipped)


def _rel(a, b):
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-3)


class TestDistributedMatchesSingle:
    """compare.py:36-47 — per-iteration loss equality, distributed vs not."""

    def test_o0_dp2_trace_equals_single(self):
        single = run_trace("O0")
        dist = run_trace("O0", dp=True)
        np.testing.assert_allclose(dist[0], single[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(dist[1], single[1], rtol=1e-4, atol=1e-6)

    def test_o2_bf16_dp2_trace_matches_single(self):
        single = run_trace("O2", "bfloat16")
        dist = run_trace("O2", "bfloat16", dp=True)
        # bf16 compute reassociates across shards; tolerance is half-precision
        assert _rel(dist[0], single[0]).max() < 3e-2


class TestAmpTracksO0:
    """The O-level x keep-BN cross-product (run_test.sh:29-49): every bf16
    config's trace must follow the fp32 baseline."""

    @pytest.mark.parametrize("opt_level", ["O1", "O2", "O3"])
    @pytest.mark.parametrize("keep_bn", [True, False])
    def test_bf16_trace_tracks_o0(self, opt_level, keep_bn):
        base_l, base_g, _ = run_trace("O0")
        l, g, sk = run_trace(opt_level, "bfloat16", keep_bn=keep_bn)
        assert not sk.any()  # bf16 never overflows at these magnitudes
        assert np.isfinite(l).all()
        assert _rel(l, base_l).max() < 0.15, (l, base_l)
        assert _rel(g, base_g).max() < 0.35, (g, base_g)
        assert l[-1] < l[0]  # actually converging, not just finite

    def test_fused_adam_o2_tracks_o0_adam(self):
        """Ref ADAM_ARGS config: --opt-level O2 --keep-batchnorm-fp32 False
        --fused-adam (run_test.sh:29)."""
        base_l, _, _ = run_trace("O0", fused=True)
        l, _, sk = run_trace("O2", "bfloat16", keep_bn=False, fused=True)
        assert not sk.any()
        assert _rel(l, base_l).max() < 0.15
        assert l[-1] < l[0]


class TestLossScaleInvariance:
    """run_test.sh loss_scales x fp16: the update must not depend on a
    static scale's magnitude, and dynamic must train through backoffs."""

    def test_fp16_static_scales_match(self):
        l1, g1, s1 = run_trace("O2", "float16", loss_scale=1.0)
        l128, g128, s128 = run_trace("O2", "float16", loss_scale=128.0)
        assert not s1.any() and not s128.any()
        np.testing.assert_allclose(l1, l128, rtol=2e-3)
        np.testing.assert_allclose(g1, g128, rtol=5e-3, atol=1e-4)

    def test_fp16_dynamic_trains(self):
        l, _, sk = run_trace("O2", "float16", loss_scale="dynamic",
                             steps=STEPS + 4)
        assert sk.sum() <= (STEPS + 4) // 2  # backoffs allowed, runaway not
        done = ~sk
        assert l[done][-1] < l[done][0]

    def test_fp16_static_tracks_o0(self):
        base_l, _, _ = run_trace("O0")
        l, _, sk = run_trace("O2", "float16", loss_scale=128.0)
        assert not sk.any()
        assert _rel(l, base_l).max() < 0.15
