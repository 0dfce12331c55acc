"""``apex_tpu.training``: the one builder of the GPT step. Its programs are
pinned by their lowered text, its one decision (``vmap`` over microbatches, or
a loop where the model has expert layers) against the other side, its place
in the package by what importing it loads, and the harnesses that timed steps
beside ``perf/run.py`` by their absence. CPU, tiny sizes."""

import hashlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import gpt_mtp_loss_fn
from apex_tpu.models.arch import described_model
from apex_tpu.parallel import parallel_state
from apex_tpu.training import GPTTargetConfig, build_gpt_training

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32
GPT2 = dict(vocab=128, layers=2, hidden=64, heads=4, seq_len=SEQ,
            micro_batch=1, global_batch=2)
#: a tiny file of JoyAI-LLM-Flash's keys (tests/test_latent_moe.py's)
ARCH = dict(
    model_type="joyai_llm_flash", hidden_size=64, num_attention_heads=4,
    num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, n_shared_experts=1,
    num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=2.5,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rms_norm_eps=1e-6, rope_theta=32e6,
    rope_interleave=True, vocab_size=128, num_nextn_predict_layers=1)


def _described(arch=ARCH, **share):
    sizes, model = described_model(arch, layers_kept=3, vocab_rows=128,
                                   **share)
    return dict(**sizes, model=model, seq_len=SEQ, micro_batch=1,
                global_batch=2)


def _experts(**kw):
    """Latent attention, a leading dense layer, routed + shared experts, a
    multi-token-prediction block, a moving router bias: the JoyAI cell's
    shape of step."""
    return dict(_described(experts_held=4, first_expert=4,
                           router_bias_update_speed=0.01), **kw)


def _lowered(cfg, **kw):
    try:
        tr = build_gpt_training(cfg)
        state, bag = jax.eval_shape(tr.init_state), jax.eval_shape(tr.init_bag)
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        return tr.train_step.lower(
            *state, bag, tr.batch_struct(), tr.batch_struct(), scalar,
            scalar).as_text(**kw)
    finally:
        parallel_state.destroy_model_parallel()


# sha256 of the StableHLO text of each tiny step, taken on the tree before
# the builder moved here and lost its second path (PR 29); the first is the
# hash PRs 27 and 28 held the GPT-2-shaped step to. The two ``experts-*``
# steps moved with PR 31, which meant to move them: latent attention hands
# the flash entry its projections' outputs (no concatenate, broadcast or
# transpose of q, k, v and the context); the five ``gpt2*`` ones stayed
PINNED = {
    "gpt2": (
        dict(GPT2, max_devices=1),
        "265319a6949302f9d066fbe2bd96ea59927ac80c1dbe00d2c54b51558f8dab00"),
    "gpt2-layer-rms": (
        dict(GPT2, max_devices=1, collect_layer_rms=True),
        "4a248df869f778b4baf2c0651d8cbd011655bb5dc238e75f9fa06c5404a5042e"),
    "gpt2-dp4": (    # the four-chip cell's shape of step: DDP, one microbatch
        dict(GPT2, global_batch=4, max_devices=4),
        "4db6fbdadac85d32808a9579c76fd11e41d5a3c0715fd28ce0d608439854d547"),
    "gpt2-dp2-int8": (
        dict(GPT2, max_devices=2, compression="int8"),
        "a337905a88ae7c6ce3124daf422675fbb16d4e98633597f1c6f7d1a5b7d5b30b"),
    "gpt2-dp2-zero-layer-rms": (
        dict(GPT2, global_batch=4, max_devices=2, zero=True,
             collect_layer_rms=True),
        "ce2f661fdf0cf44b8c05df366c8c20c9e0aa13621dc54984563f1af14a11ad34"),
    "experts-mtp-bias-choices": (
        _experts(max_devices=1, collect_expert_choices=True),
        "c77b26c7ea0c38299aed3bb9b296bd12213eada9ac8bf50e711cef44e90484a2"),
    "experts-mtp-dp2-layer-rms": (
        _experts(global_batch=4, max_devices=2, collect_layer_rms=True),
        "ebe4a623c18a03f8ba609544dc6bcc9d845ad1a736066583e876dd82ab3d8bcc"),
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_the_step_lowers_to_the_text_it_lowered_to(case):
    """A change that means to move one of these programs moves its hash with
    it; one that does not (a refactor of the builder, a new kind of model)
    leaves them all."""
    cfg, sha = PINNED[case]
    text = _lowered(GPTTargetConfig(**cfg))
    assert hashlib.sha256(text.encode()).hexdigest() == sha


def test_microbatches_are_vmapped_unless_the_model_has_expert_layers():
    """The builder's one decision, read off the lowered op names: the
    experts' grouped matmul takes its group sizes as scalars, so only a
    model with expert layers runs one microbatch after another."""
    dense = dict(ARCH, first_k_dense_replace=3)
    for cfg, vmapped in ((dict(GPT2), True), (_described(dense), True),
                         (_experts(), False)):
        text = _lowered(GPTTargetConfig(**cfg, max_devices=1),
                        debug_info=True)
        assert ("vmap(GPTModel)" in text) == vmapped, cfg.get("model")


def test_a_described_model_without_expert_layers_trains_under_vmap_like_the_loop():
    """Latent attention + dense MLPs + a multi-token-prediction block: the
    vmapped step's loss, loss terms, per-layer RMS and gradients are those of
    the same weights run one microbatch after another."""
    from apex_tpu.monitor.metrics import read_bag

    dense = _described(dict(ARCH, first_k_dense_replace=3))
    # fp32 compute, so that the two orders of summation agree to fp32
    dense["model"] = dict(dense["model"], compute_dtype=jnp.float32)
    cfg = GPTTargetConfig(**dense, max_devices=1, collect_layer_rms=True)
    try:
        tr = build_gpt_training(cfg)
        assert tr.num_micro == 2 and "loss_mtp" in tr.metric_spec
        assert "moe_rows_here" not in tr.metric_spec
        params, opt_state, scaler_state, sent_state = tr.init_state()
        kept = jax.tree_util.tree_map(jnp.copy, params)  # the step donates
        rng = np.random.default_rng(4)
        t = rng.integers(0, 128, (2, SEQ + 1)).astype(np.int32)
        tok, lab = tr.reshape_batch(t[:, :-1], t[:, 1:])
        out = tr.train_step(params, opt_state, scaler_state, sent_state,
                            tr.init_bag(), tok, lab, jnp.float32(0),
                            jnp.float32(1))

        def one(p, i):
            (losses, mtp), inter = tr.model.apply(
                p, tok[i], labels=lab[i], mutable=["intermediates"])
            total, main, second = gpt_mtp_loss_fn(
                losses, mtp, tr.transformer_config.mtp_loss_coeff)
            return total, (main, second, inter["intermediates"])

        each = [jax.value_and_grad(one, has_aux=True)(kept, i)
                for i in range(2)]
    finally:
        parallel_state.destroy_model_parallel()
    tol = dict(rtol=1e-5, atol=1e-6)
    (l0, (m0, s0, i0)), g0 = each[0]
    (l1, (m1, s1, i1)), g1 = each[1]
    bag = read_bag(out[4])
    np.testing.assert_allclose(float(out[5]), float(l0 + l1) / 2, **tol)
    np.testing.assert_allclose(bag["loss_main"], float(m0 + m1) / 2, **tol)
    np.testing.assert_allclose(bag["loss_mtp"], float(s0 + s1) / 2, **tol)
    # first Adam step: exp_avg = (1 - beta1) x the gradient
    want = jax.tree_util.tree_map(lambda a, b: 0.1 * (a + b) / 2, g0, g1)
    for got, w in zip(jax.tree_util.tree_leaves(out[1].exp_avg),
                      jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            got, w, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(w))) + 1e-9)

    def rms(inter):  # the trunk's three layers in depth order, then the block's
        trunk = [inter["transformer"][f"layer_{i}"]["layer_out_rms"][0]
                 for i in range(3)]
        return np.asarray(
            trunk + jax.tree_util.tree_leaves(inter["mtp"]), np.float32)

    np.testing.assert_allclose(
        out[-1], np.sqrt((np.square(rms(i0)) + np.square(rms(i1))) / 2),
        rtol=1e-5)


# -- where the builder lives --------------------------------------------------


def test_training_loads_without_the_replayer():
    """``apex_tpu.training`` sits below the replayer: importing it loads
    nothing of ``apex_tpu.resilience.replay`` (and, being lazy, no jax)."""
    code = ("import sys, apex_tpu.training as t; "
            "assert t.build_gpt_training and t.GPTTargetConfig().layers == 4; "
            "bad = [m for m in sys.modules if m.startswith("
            "'apex_tpu.resilience.replay') or m == 'jax']; "
            "assert not bad, bad")
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]


@pytest.mark.parametrize(
    "name", ["GPTTargetConfig", "GPTTraining", "build_gpt_training"])
def test_the_replayers_module_still_hands_out_the_same_objects(name):
    """``perf/drivers/*.py`` read the builder from
    ``resilience.replay.targets`` and ``tests/perf`` patches it there, until
    a ``benchmark`` PR points them here."""
    import apex_tpu.training as training
    from apex_tpu.resilience import replay
    from apex_tpu.resilience.replay import targets

    assert getattr(targets, name) is getattr(training, name)
    if name != "GPTTraining":
        assert getattr(replay, name) is getattr(training, name)


# -- one harness to time a step -----------------------------------------------

#: the files PR 29 deleted (CHANGES.md): six harnesses that timed steps
#: beside perf/run.py, and the chain-slope timer under them
GONE = ["bench.py", "benchmarks/bench_configs.py",
        "benchmarks/bench_optimizers.py", "benchmarks/bench_small_shapes.py",
        "benchmarks/bench_pipeline_memory.py",
        "examples/multihead_attn/perf_test_multihead_attn.py",
        "apex_tpu/utils/benchmarking.py", "tests/test_benchmarking.py"]
NAMED = re.compile(
    r"bench_configs|bench_optimizers|bench_small_shapes|bench_pipeline_memory"
    r"|(?<!contrib/)examples/multihead_attn/perf_test|test_benchmarking"
    r"|utils[./]benchmarking|(?<![\w/])bench\.py")
#: the PR's own records, and the documents that describe the reference this
#: repository was modelled on (whose MHA harness has the example's name)
EXEMPT = {"CHANGES.md", "ROADMAP.md", "ISSUE.md", "SURVEY.md", "BASELINE.md",
          "PAPER.md", "PAPERS.md", "SNIPPETS.md", "ADVICE.md",
          os.path.join("tests", "test_training_step.py")}


def _sources():
    """Every .py and .md a checkout holds: what ``.gitignore`` lists as a
    directory is left out."""
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f
                   if line.strip().endswith("/")}
    for here, dirs, files in os.walk(ROOT):
        rel = os.path.relpath(here, ROOT)
        dirs[:] = [d for d in dirs if d != ".git" and d not in ignored
                   and os.path.normpath(os.path.join(rel, d)) not in ignored]
        for name in files:
            if name.endswith((".py", ".md")):
                yield os.path.normpath(os.path.join(rel, name))


def test_nothing_names_the_harnesses_that_went():
    assert not [p for p in GONE if os.path.exists(os.path.join(ROOT, p))]
    named = {}
    for path in _sources():
        if path in EXEMPT:
            continue
        with open(os.path.join(ROOT, path), errors="replace") as f:
            hits = NAMED.findall(f.read())
        if hits:
            named[path] = sorted(set(hits))
    assert not named, named
