"""Core parallel primitives under jax's CHECKED shard_map (check_vma=True,
the default) — the mode every fresh user hits.

The package's own tests historically ran check_vma=False; probing under
checked mode (2026-07-31) found three latent type failures, all fixed and
pinned here with checked-vs-unchecked numeric parity:

- ring attention's (b, 0) bias placeholder entered the ring scan carry
  unvarying and left varying after ppermute (scan typecheck);
- the pipeline schedules' zero boundary-activation carry had the same
  mismatch (fixed-point vma derived from eval_shape in _varying_zeros);
- the TP mappings' bwd rules produced wrongly-typed cotangents
  (scatter bwds need the invariant all_gather; reduce_from's bwd must
  pvary the invarying cotangent).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.ring_attention import ring_attention


@pytest.fixture
def cp_mesh():
    return Mesh(np.asarray(jax.devices()), ("cp",))


def _ring_loss_grads(mesh, check_vma, **ring_kw):
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 32, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 32, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 32, 8))

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, None, "cp"),) * 3,
        out_specs=P(None, None, "cp"),
        check_vma=check_vma,
    )
    def grads(q, k, v):
        def loss(q, k, v):
            return jnp.sum(jnp.sin(ring_attention(
                q, k, v, axis_name="cp", **ring_kw)))

        return jax.grad(loss)(q, k, v)

    return np.asarray(grads(q, k, v))


@pytest.mark.parametrize("ring_kw", [
    dict(causal=True),
    dict(causal=True, window=8),
    dict(causal=True, zigzag=True),
])
def test_ring_attention_checked_matches_unchecked(cp_mesh, ring_kw):
    got = _ring_loss_grads(cp_mesh, True, **ring_kw)
    want = _ring_loss_grads(cp_mesh, False, **ring_kw)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_pipeline_1f1b_checked_matches_unchecked():
    from apex_tpu.parallel.pipeline.schedules import (
        forward_backward_pipelining_without_interleaving,
    )

    mesh = Mesh(np.asarray(jax.devices()), ("pp",))
    hid, mb, M = 8, 2, 8
    xs = jax.random.normal(jax.random.PRNGKey(0), (M, mb, hid))
    ts = jax.random.normal(jax.random.PRNGKey(3), (M, mb, hid))

    def stage_fn(params, x):
        return jnp.tanh(x @ params)

    def loss_fn(x, t):
        return jnp.mean((x - t) ** 2)

    def run(check_vma):
        @jax.jit
        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(P(), P()),
            out_specs=(P(), P("pp")), check_vma=check_vma,
        )
        def go(xs, ts):
            params = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(1),
                                   jax.lax.axis_index("pp")),
                (hid, hid),
            ) * 0.3
            loss, _, grads = forward_backward_pipelining_without_interleaving(
                stage_fn, loss_fn, params, xs, ts, axis_name="pp"
            )
            return jax.lax.pmean(loss, "pp"), grads[None]

        return go(xs, ts)

    l1, g1 = run(True)
    l0, g0 = run(False)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=1e-6,
                               atol=1e-7)


def test_gpt_pp_tp_sp_full_step_checked():
    """The dryrun-class integration (pipelined parallel transformer with
    SP) must compile AND produce finite loss/grads under default checked
    shard_map — the three latent fixes compose here."""
    from apex_tpu.models.gpt_pipeline import build_gpt_pipeline
    from apex_tpu.parallel import parallel_state
    from apex_tpu.parallel.pipeline import forward_backward_with_pre_post
    from apex_tpu.transformer import TransformerConfig

    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2, pipeline_model_parallel_size=2,
    )
    vocab, seq, hidden, mb, num_micro = 64, 16, 32, 2, 2
    cfg = TransformerConfig(
        num_layers=4, hidden_size=hidden, num_attention_heads=4,
        vocab_size=vocab, max_position_embeddings=seq,
        hidden_dropout=0.0, attention_dropout=0.0,
        sequence_parallel=True, compute_dtype=jnp.float32,
    )
    parts = build_gpt_pipeline(cfg, 2)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (num_micro, mb * 2, seq), 0, vocab)
    labels = jnp.roll(tokens, -1, axis=2)

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, "dp"), P(None, "dp")), out_specs=(P(), P()),
    )
    def step(tokens, labels):
        init_key = jax.random.PRNGKey(0)
        pre = parts.embed.init(init_key, tokens[0])["params"]
        h0 = parts.pre_fn(pre, tokens[0])
        r = jax.lax.axis_index("pp")
        stage = parts.chunk.init(
            jax.random.fold_in(jax.random.fold_in(init_key, 7), r), h0
        )["params"]
        params = {"pre": pre, "stages": stage,
                  "post": parts.init_post(jax.random.fold_in(init_key, 9))}
        loss, _, grads = forward_backward_with_pre_post(
            parts.pre_fn, parts.stage_fn, parts.post_loss_fn, params,
            tokens, labels, axis_name="pp",
        )
        gnorm = sum(
            jnp.sum(jnp.square(g))
            for g in jax.tree_util.tree_leaves(grads)
        )
        for ax in ("tp", "cp", "dp", "pp"):
            loss = jax.lax.pmean(loss, ax)
            gnorm = jax.lax.pmean(gnorm, ax)
        return loss, gnorm

    loss, gnorm = step(tokens, labels)
    assert np.isfinite(float(loss))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0.0
    parallel_state.destroy_model_parallel()


def test_tp_linears_checked_match_unchecked():
    """Column+Row parallel linears (the mappings' bwd rules) produce the
    same grads in both modes."""
    from apex_tpu.parallel import parallel_state
    from apex_tpu.parallel.layers import (
        ColumnParallelLinear,
        RowParallelLinear,
    )

    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=8,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16))

    def run(check_vma):
        col = ColumnParallelLinear(output_size=32, gather_output=False)
        row = RowParallelLinear(output_size=16, input_is_parallel=True)

        @jax.jit
        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=check_vma,
        )
        def grads(x):
            from apex_tpu.parallel import pvary_params

            kc = jax.random.fold_in(jax.random.PRNGKey(1),
                                    jax.lax.axis_index("tp"))
            # zeros-init SHARDED params read as replicated under checked
            # vma even though each rank holds a distinct slice: mark them
            # varying or grads auto-psum over tp (the failure pinned
            # here). Column kernel+bias both shard the output dim; row
            # kernel shards the input dim but its bias is applied AFTER
            # the reduction — genuinely replicated, so it must stay
            # invarying (pvarying it makes the output spuriously varying)
            pc = pvary_params(col.init(kc, x), "tp")
            h = col.apply(pc, x)
            pr = row.init(jax.random.fold_in(kc, 2), h)
            pr = {"params": {
                "kernel": pvary_params(pr["params"]["kernel"], "tp"),
                "bias": pr["params"]["bias"],
            }}

            def loss(pc, pr):
                out = row.apply(pr, col.apply(pc, x))
                return jnp.sum(jnp.sin(out))

            gc, gr = jax.grad(loss, argnums=(0, 1))(pc, pr)
            total = sum(
                jnp.sum(jnp.abs(l))
                for l in jax.tree_util.tree_leaves((gc, gr))
            )
            return jax.lax.pmean(total, "tp")

        return float(grads(x))

    got, want = run(True), run(False)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    parallel_state.destroy_model_parallel()


@pytest.mark.parametrize("check_vma", [False, True])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_vocab_parallel_ce_grads_match_dense(check_vma, smoothing):
    """The CE backward is hand-written (custom_vjp): plain autodiff
    through the forward's psums under check_vma=False double-counted
    (tp x the dense gradient, measured 8x on this mesh — the psum
    transposes to a psum, so every rank's redundant loss copy
    contributed). Both modes must produce the DENSE gradient exactly."""
    from apex_tpu.parallel import parallel_state
    from apex_tpu.parallel.cross_entropy import vocab_parallel_cross_entropy

    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=8
    )
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 64))
    targets = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0, 64)

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, None, "tp"), P()),
        out_specs=(P(), P(None, None, "tp")),
        check_vma=check_vma,
    )
    def run(lg, tg):
        def loss(lg):
            return jnp.mean(vocab_parallel_cross_entropy(
                lg, tg, label_smoothing=smoothing))

        l, g = jax.value_and_grad(loss)(lg)
        return jax.lax.pmean(l, ("dp", "pp", "cp", "tp")) if check_vma \
            else jax.lax.pmean(l, "tp"), g

    def dense_loss(lg):
        lf = lg.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        ce = lse - jnp.take_along_axis(lf, targets[..., None], -1)[..., 0]
        if smoothing > 0.0:
            ce = (1 - smoothing) * ce + smoothing * (
                lse - jnp.mean(lf, axis=-1))
        return jnp.mean(ce)

    l, g = run(logits, targets)
    dl, dg = jax.value_and_grad(dense_loss)(logits)
    np.testing.assert_allclose(float(l), float(dl), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g), np.asarray(dg),
                               rtol=1e-5, atol=1e-6)
    parallel_state.destroy_model_parallel()


def test_fwd_bwd_pre_post_checked_matches_unchecked():
    """forward_backward_with_pre_post's replicated pre/post grad combine
    must not double-psum under checked vma (the grad transpose already
    summed them over pp; the explicit tied-embedding psum now dispatches
    on the vma type). Loss AND grads must match the unchecked run."""
    from apex_tpu.models.gpt_pipeline import build_gpt_pipeline
    from apex_tpu.parallel import parallel_state
    from apex_tpu.parallel.pipeline import forward_backward_with_pre_post
    from apex_tpu.transformer import TransformerConfig

    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size=2,
    )
    vocab, seq, hidden, mb, num_micro = 64, 16, 32, 2, 2
    cfg = TransformerConfig(
        num_layers=2, hidden_size=hidden, num_attention_heads=4,
        vocab_size=vocab, max_position_embeddings=seq,
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.float32,
    )
    parts = build_gpt_pipeline(cfg, 2)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (num_micro, mb, seq), 0, vocab)
    labels = jnp.roll(tokens, -1, axis=2)

    def run(check_vma):
        @jax.jit
        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(P(), P()),
            out_specs=(P(), P(), P()), check_vma=check_vma,
        )
        def step(tokens, labels):
            init_key = jax.random.PRNGKey(0)
            pre = parts.embed.init(init_key, tokens[0])["params"]
            h0 = parts.pre_fn(pre, tokens[0])
            r = jax.lax.axis_index("pp")
            stage = parts.chunk.init(
                jax.random.fold_in(jax.random.fold_in(init_key, 7), r), h0
            )["params"]
            params = {"pre": pre, "stages": stage,
                      "post": parts.init_post(jax.random.fold_in(init_key, 9))}
            loss, _, grads = forward_backward_with_pre_post(
                parts.pre_fn, parts.stage_fn, parts.post_loss_fn, params,
                tokens, labels, axis_name="pp",
            )
            pre_norm = sum(
                jnp.sum(jnp.abs(g))
                for g in jax.tree_util.tree_leaves(grads["pre"])
            )
            post_norm = sum(
                jnp.sum(jnp.abs(g))
                for g in jax.tree_util.tree_leaves(grads["post"])
            )
            def rep(x):
                for ax in ("dp", "pp", "cp", "tp"):
                    try:
                        if ax in jax.typeof(x).vma:
                            x = jax.lax.pmean(x, ax)
                    except AttributeError:
                        break
                return x
            return rep(loss), rep(pre_norm), rep(post_norm)

        return [float(v) for v in step(tokens, labels)]

    got = run(True)
    want = run(False)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    parallel_state.destroy_model_parallel()


def test_scan_carry_fixed_point_promotes_to_body_type():
    """A scan whose body widens the carry's varying axes (adding an
    axis-varying term to a replicated-zeros accumulator) fails checked
    scan's carry typecheck; scan_carry_fixed_point promotes the initial
    carry to the body's vma fixed point and the result matches the
    direct computation."""
    from apex_tpu.parallel import scan_carry_fixed_point

    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    x = jnp.arange(8.0)

    def run(warm):
        @jax.jit
        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P()
        )
        def f(x):
            def body(c, _):
                return c + jnp.sum(x), None  # x is dp-varying; c starts not

            c0 = jnp.zeros(())
            if warm:
                c0 = scan_carry_fixed_point(body, c0, None)
            out, _ = jax.lax.scan(body, c0, None, length=3)
            return jax.lax.pmean(out, "dp")

        return float(f(x))

    with pytest.raises(TypeError, match="carry"):
        run(warm=False)
    np.testing.assert_allclose(run(warm=True), 3 * float(jnp.mean(x)))


def test_vma_cond_mixed_vma_branches_checked():
    """Branches whose outputs vary over different manual-axis sets fail a
    plain lax.cond typecheck under checked shard_map; parallel.vma_cond
    widens both outputs to their vma join INSIDE each branch and keeps
    cond's single-branch evaluation (the former known limitation in
    docs/parallel.md)."""
    from apex_tpu.parallel import vma_cond

    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    n = len(jax.devices())
    x = jnp.arange(float(n))

    def run(cond_impl, flag):
        @jax.jit
        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(P("dp"), P()),
            out_specs=P("dp"),
        )
        def f(x, flag):
            # true: dp-INVARIANT (psum); false: dp-varying — mixed types
            return cond_impl(
                flag,
                lambda o: jax.lax.psum(o, "dp"),
                lambda o: 2.0 * o,
                x,
            )

        return np.asarray(f(x, flag))

    with pytest.raises((TypeError, ValueError)):
        run(jax.lax.cond, jnp.bool_(True))
    total = float(jnp.sum(x))
    np.testing.assert_allclose(run(vma_cond, jnp.bool_(True)),
                               np.full(n, total))
    np.testing.assert_allclose(run(vma_cond, jnp.bool_(False)),
                               2.0 * np.asarray(x))


def test_amp_optimizer_skip_step_checked():
    """AmpOptimizer's overflow skip-step under checked shard_map: grads
    arrive dp-varying while the master/inner state is replicated — the
    exact mixed-vma cond vma_cond exists for (previously AmpOptimizer
    required check_vma=False meshes)."""
    import optax

    from apex_tpu import amp

    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    n = len(jax.devices())
    params = {"w": jnp.ones((4,), jnp.float32)}

    def run(bad):
        tx = optax.sgd(0.1)
        casted, amp_opt, _ = amp.initialize(params, tx, opt_level="O2")
        state = amp_opt.init(casted)
        scale = float(amp_opt.scaler.scale(state.scaler, jnp.float32(1.0)))
        data = jnp.arange(1.0, float(n) + 1.0)  # per-rank scalar 1..n

        @jax.jit
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P("dp"), out_specs=(P(), P()))
        def step(d):
            per_rank = jnp.inf if bad else 1.0
            grads = {"w": jnp.full((4,), scale * per_rank * d[0],
                                   jnp.float32)}
            new_params, new_state, info = amp_opt.step(grads, state, casted)
            w = jax.lax.pmean(new_params["w"].astype(jnp.float32), "dp")
            return w, jax.lax.pmean(
                info["found_inf"].astype(jnp.float32), "dp")

        return step(data)

    w_bad, inf_bad = run(bad=True)
    np.testing.assert_allclose(np.asarray(w_bad), np.ones(4))  # skipped
    assert float(inf_bad) == 1.0
    w_ok, inf_ok = run(bad=False)
    # sgd(0.1) on per-rank grad r (r = 1..n), pmean'd over ranks
    expect = 1.0 - 0.1 * float(np.mean(np.arange(1.0, n + 1.0)))
    # O2 re-materializes model params in the model dtype (bf16) — compare
    # at bf16 resolution
    np.testing.assert_allclose(np.asarray(w_ok), np.full(4, expect),
                               rtol=1e-2)
    assert float(inf_ok) == 0.0
