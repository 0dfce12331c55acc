"""O1 per-op cast semantics.

Mirrors /root/reference/tests/L0/run_amp/test_basic_casts.py (whitelist ops
half, blacklist ops float, backward grads match input dtype) and
test_promotion.py (mixed-input promotion to widest, cat/stack sequence
promotion) — against the TPU cast engine (apex_tpu/amp/cast_engine.py)
instead of the patched torch namespace.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from apex_tpu import amp
from apex_tpu.amp.cast_engine import cast_ops

HALF_DTYPES = [jnp.bfloat16, jnp.float16]


def _ctx(half):
    return cast_ops(half)


class TestBasicCasts:
    """Ref TestBasicCasts (test_basic_casts.py:23-140)."""

    @pytest.mark.parametrize("half", HALF_DTYPES)
    @pytest.mark.parametrize("in_dtype", [jnp.float32, None])
    def test_matmul_is_half(self, half, in_dtype):
        in_dtype = in_dtype or half
        x = jnp.ones((4, 8), in_dtype)
        w = jnp.ones((8, 4), in_dtype)
        with _ctx(half):
            y = jnp.matmul(x, w)
        assert y.dtype == half  # ALWAYS_HALF

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_dot_general_is_half(self, half):
        """lax.dot_general is the primitive every flax Dense lowers to —
        patching it is the analogue of patching torch.addmm."""
        x = jnp.ones((4, 8), jnp.float32)
        w = jnp.ones((8, 4), jnp.float32)
        with _ctx(half):
            y = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())))
        assert y.dtype == half

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_flax_dense_is_half(self, half):
        """Ref test_linear_is_half: an nn layer (weights held outside the
        patched function) comes out half because its inner dot is patched."""
        m = nn.Dense(4)
        x = jnp.ones((2, 8), jnp.float32)
        params = m.init(jax.random.PRNGKey(0), x)
        with _ctx(half):
            y = m.apply(params, x)
        assert y.dtype == half

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_conv_is_half(self, half):
        m = nn.Conv(4, (3, 3))
        x = jnp.ones((1, 8, 8, 3), jnp.float32)
        params = m.init(jax.random.PRNGKey(0), x)
        with _ctx(half):
            y = m.apply(params, x)
        assert y.dtype == half

    @pytest.mark.parametrize("half", HALF_DTYPES)
    @pytest.mark.parametrize("in_dtype", [jnp.float32, None])
    def test_softmax_is_float(self, half, in_dtype):
        x = jnp.ones((4, 8), in_dtype or half)
        with _ctx(half):
            y = jax.nn.softmax(x, axis=-1)
        assert y.dtype == jnp.float32  # ALWAYS_FLOAT

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_sum_is_float(self, half):
        x = jnp.ones((4, 8), half)
        with _ctx(half):
            y = jnp.sum(x)
        assert y.dtype == jnp.float32

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_pow_is_float(self, half):
        x = jnp.ones((4,), half)
        with _ctx(half):
            y = jnp.power(x, 2.0)
        assert y.dtype == jnp.float32

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_exp_log_are_float(self, half):
        x = jnp.ones((4,), half)
        with _ctx(half):
            assert jnp.exp(x).dtype == jnp.float32
            assert jnp.log(x + 1.0).dtype == jnp.float32

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_relu_is_match(self, half):
        """Ref test_relu_is_match: unlisted ops preserve input dtype."""
        for dt in (half, jnp.float32):
            x = jnp.ones((4,), dt)
            with _ctx(half):
                assert jax.nn.relu(x).dtype == dt

    def test_backward_grads_match_input_dtype(self):
        """Ref run_layer_test's backward check: d/dx of a whitelist op on an
        fp32 input arrives fp32 (the cast's VJP casts back)."""
        x = jnp.ones((4, 8), jnp.float32)
        w = jnp.ones((8, 4), jnp.float32)
        with _ctx(jnp.bfloat16):
            g = jax.grad(lambda a: jnp.matmul(a, w).astype(jnp.float32).sum())(x)
        assert g.dtype == jnp.float32

    def test_inactive_outside_context(self):
        x = jnp.ones((4, 8), jnp.float32)
        w = jnp.ones((8, 4), jnp.float32)
        assert jnp.matmul(x, w).dtype == jnp.float32
        with _ctx(jnp.bfloat16):
            pass
        assert jnp.matmul(x, w).dtype == jnp.float32
        assert not hasattr(jnp.matmul, "__wrapped_by_apex_tpu_amp__")

    def test_casts_compile_into_jit(self):
        """Tracing inside the context bakes the casts into the jaxpr —
        the compiled fn keeps O1 behavior outside the context (the torch
        analogue: a cuda graph captured while the handle was active)."""
        w = jnp.ones((8, 4), jnp.float32)
        with _ctx(jnp.bfloat16):
            f = jax.jit(lambda a: jnp.matmul(a, w))
            y = f(jnp.ones((4, 8), jnp.float32))  # traced inside
        assert y.dtype == jnp.bfloat16
        assert f(jnp.ones((4, 8), jnp.float32)).dtype == jnp.bfloat16


class TestPromotion:
    """Ref TestPromotion (test_promotion.py:42-75)."""

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_atan2_matches_widest(self, half):
        a = jnp.ones((4,), half)
        b = jnp.ones((4,), jnp.float32)
        with _ctx(half):
            assert jnp.arctan2(a, b).dtype == jnp.float32
            assert jnp.arctan2(b, a).dtype == jnp.float32

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_mul_matches_widest(self, half):
        a = jnp.ones((4,), half)
        b = jnp.ones((4,), jnp.float32)
        with _ctx(half):
            assert jnp.multiply(a, b).dtype == jnp.float32

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_single_type_untouched(self, half):
        a = jnp.ones((4,), half)
        b = jnp.ones((4,), half)
        with _ctx(half):
            assert jnp.add(a, b).dtype == half

    @pytest.mark.parametrize("half", HALF_DTYPES)
    def test_cat_matches_widest(self, half):
        """Ref test_cat_matches_widest via SEQUENCE_CASTS."""
        seq = [jnp.ones((4,), half), jnp.ones((4,), jnp.float32)]
        with _ctx(half):
            assert jnp.concatenate(seq).dtype == jnp.float32
            assert jnp.stack(seq).dtype == jnp.float32

    def test_nested_same_dtype_ok_mismatch_raises(self):
        with _ctx(jnp.bfloat16):
            with _ctx(jnp.bfloat16):
                assert jnp.sum(jnp.ones((2,), jnp.bfloat16)).dtype == jnp.float32
            with pytest.raises(ValueError, match="different half dtypes"):
                with _ctx(jnp.float16):
                    pass
        # fully restored after nesting
        assert not hasattr(jnp.sum, "__wrapped_by_apex_tpu_amp__")


class TestO1Policy:
    """End-to-end: the O1 policy drives the engine through wrap_apply."""

    def test_o1_has_patch_functions(self):
        assert amp.O1().patch_functions
        assert not amp.O2().patch_functions and not amp.O0().patch_functions

    def test_o1_wrap_apply_blacklist_inside_model(self):
        """A model whose head is a blacklisted op produces fp32 internally
        under O1 even though inputs were cast half."""
        policy = amp.O1(jnp.bfloat16)
        seen = {}

        def apply_fn(params, x):
            y = jnp.matmul(x, params["w"])  # whitelist -> half
            seen["mm"] = y.dtype
            z = jnp.sum(y)  # blacklist -> fp32
            seen["sum"] = z.dtype
            return z

        params = {"w": jnp.ones((8, 4), jnp.float32)}
        out = policy.wrap_apply(apply_fn)(params, jnp.ones((2, 8), jnp.float32))
        assert seen["mm"] == jnp.bfloat16
        assert seen["sum"] == jnp.float32
        assert out.dtype == jnp.float32

    def test_o2_wrap_apply_does_not_patch(self):
        policy = amp.O2(jnp.bfloat16)
        seen = {}

        def apply_fn(params, x):
            seen["sum"] = jnp.sum(x).dtype
            return x

        policy.wrap_apply(apply_fn)({}, jnp.ones((2,), jnp.float32))
        assert seen["sum"] == jnp.bfloat16  # no fp32 blacklist under O2


class TestUserRegistries:
    """Ref amp/amp.py:33-71: user-annotated functions join the cast lists."""

    def test_half_and_float_decorators(self):
        from apex_tpu.amp import float_function, half_function

        @half_function
        def my_matmul(a, b):
            return a @ b

        @float_function
        def my_reduce(x):
            return x.sum()

        a = jnp.ones((4, 4), jnp.float32)
        h = jnp.ones((4,), jnp.bfloat16)
        # inactive outside a context
        assert my_matmul(a, a).dtype == jnp.float32
        assert my_reduce(h).dtype == jnp.bfloat16
        with _ctx(jnp.bfloat16):
            assert my_matmul(a, a).dtype == jnp.bfloat16
            assert my_reduce(h).dtype == jnp.float32

    def test_promote_decorator(self):
        from apex_tpu.amp import promote_function

        @promote_function
        def my_mix(a, b):
            return a * b

        with _ctx(jnp.bfloat16):
            out = my_mix(jnp.ones((2,), jnp.bfloat16), jnp.ones((2,), jnp.float32))
        assert out.dtype == jnp.float32

    def test_register_namespace_functions(self):
        import types

        from apex_tpu.amp import (
            register_float_function,
            register_half_function,
            register_promote_function,
        )

        ns = types.SimpleNamespace(
            mm=lambda a, b: a @ b,
            red=lambda x: x.sum(),
            mix=lambda a, b: a + b,
        )
        register_half_function(ns, "mm")
        register_float_function(ns, "red")
        register_promote_function(ns, "mix")
        a32 = jnp.ones((4, 4), jnp.float32)
        h = jnp.ones((4,), jnp.bfloat16)
        with _ctx(jnp.bfloat16):
            assert ns.mm(a32, a32).dtype == jnp.bfloat16
            assert ns.red(h).dtype == jnp.float32
            assert ns.mix(h, jnp.ones((4,), jnp.float32)).dtype == jnp.float32
        # restored on exit, like the built-in lists
        assert ns.mm(a32, a32).dtype == jnp.float32
        assert ns.red(h).dtype == jnp.bfloat16

    def test_register_missing_name_raises(self):
        import types

        from apex_tpu.amp import register_half_function

        with pytest.raises(ValueError, match="No function named"):
            register_half_function(types.SimpleNamespace(), "nope")

    def test_user_registration_overrides_builtin_list(self):
        """register_float_function on an FP16-whitelisted op must NOT
        round-trip args through the half dtype (precision check: 1+2^-12
        survives fp32 but rounds to 1.0 in bf16)."""
        from apex_tpu.amp import register_float_function
        from apex_tpu.amp import cast_engine

        register_float_function(jnp, "einsum")
        try:
            a = jnp.full((1, 1), 1.0 + 2.0**-12, jnp.float32)
            with _ctx(jnp.bfloat16):
                out = jnp.einsum("ij,jk->ik", a, a)
            assert out.dtype == jnp.float32
            assert float(out[0, 0]) > 1.0  # bf16 truncation would give 1.0
        finally:
            cast_engine._USER_FP32_REGISTRY.remove((jnp, "einsum"))

    def test_patch_failure_unwinds_cleanly(self):
        import types

        from apex_tpu.amp import register_half_function
        from apex_tpu.amp import cast_engine

        ns = types.SimpleNamespace(fn=lambda x: x)
        register_half_function(ns, "fn")
        del ns.fn  # vanishes before the next context enter
        try:
            with pytest.raises(AttributeError):
                with _ctx(jnp.bfloat16):
                    pass
            # nothing leaked: built-ins restored, a fresh context works
            assert not hasattr(jnp.matmul, "__wrapped_by_apex_tpu_amp__")
            ns.fn = lambda x: x
            with _ctx(jnp.bfloat16):
                x = jnp.ones((2, 2), jnp.float32)
                assert jnp.matmul(x, x).dtype == jnp.bfloat16
        finally:
            cast_engine._USER_FP16_REGISTRY.remove((ns, "fn"))

    def test_user_override_on_flax_module_call(self):
        """A float registration on a listed flax layer must defeat the
        built-in half-output wrapper too."""
        from apex_tpu.amp import register_float_function
        from apex_tpu.amp import cast_engine

        register_float_function(nn.Dense, "__call__")
        try:
            m = nn.Dense(4)
            x = jnp.ones((2, 8), jnp.float32)
            params = m.init(jax.random.PRNGKey(0), x)
            with _ctx(jnp.bfloat16):
                assert m.apply(params, x).dtype == jnp.float32
        finally:
            cast_engine._USER_FP32_REGISTRY.remove((nn.Dense, "__call__"))
        # built-in behavior restored
        params = nn.Dense(4).init(jax.random.PRNGKey(0), jnp.ones((2, 8)))
        with _ctx(jnp.bfloat16):
            assert nn.Dense(4).apply(params, jnp.ones((2, 8))).dtype == jnp.bfloat16

    def test_latest_registration_wins(self):
        import types

        from apex_tpu.amp import register_float_function, register_half_function
        from apex_tpu.amp import cast_engine

        ns = types.SimpleNamespace(f=lambda x: x)
        register_half_function(ns, "f")
        register_float_function(ns, "f")  # most recent intent: fp32
        try:
            with _ctx(jnp.bfloat16):
                assert ns.f(jnp.ones((2,), jnp.bfloat16)).dtype == jnp.float32
        finally:
            cast_engine._USER_FP32_REGISTRY.remove((ns, "f"))
        assert (ns, "f") not in cast_engine._USER_FP16_REGISTRY


class TestCastThroughRNNScan:
    """O1 cast behavior through the rnn/ scan cells (ref:
    apex/amp/rnn_compat.py + SEQUENCE_CASTS in
    apex/amp/lists/torch_overrides.py — the reference needed special RNN
    handling because cuDNN RNNs bypass the functional overrides; here the
    cells are plain flax modules whose gate GEMMs go through the patched
    ``lax.dot_general``, and the contract to pin is that the scan CARRY
    keeps one stable dtype across steps while the GEMMs run in half)."""

    @pytest.mark.parametrize("model_cls", ["LSTM", "GRU", "mLSTM"])
    def test_scan_carry_stable_and_gemms_halved(self, rng, model_cls):
        from apex_tpu import rnn as rnn_mod

        model = getattr(rnn_mod, model_cls)(4, 8)
        xs = jax.random.normal(rng, (5, 2, 4), jnp.float32)
        params = model.init(jax.random.PRNGKey(0), xs)

        # traces (carry dtype stable across scan steps) AND runs under O1
        with _ctx(jnp.bfloat16):
            ys, carry = jax.jit(model.apply)(params, xs)
        # nonlinearity math stays fp32 (cells compute gates at fp32), so
        # outputs/carries are fp32 even with bf16 GEMMs
        assert ys.dtype == jnp.float32
        for leaf in jax.tree_util.tree_leaves(carry):
            assert leaf.dtype == jnp.float32

        # the GEMMs really ran in bf16: O1 output differs from fp32 by
        # bf16-level error but not more
        ys_ref, _ = jax.jit(model.apply)(params, xs)
        err = float(jnp.max(jnp.abs(ys - ys_ref)))
        assert 0 < err < 0.1, err

        # grads flow through the cast scan without dtype errors
        with _ctx(jnp.bfloat16):
            g = jax.grad(
                lambda p: jnp.sum(model.apply(p, xs)[0])
            )(params)
        for leaf in jax.tree_util.tree_leaves(g):
            assert jnp.all(jnp.isfinite(leaf))


def test_disable_casts_inside_cast_ops():
    """ref apex.amp.disable_casts (handle.py:164): a block inside an active
    O1 region runs at full precision, and casting resumes after."""
    from apex_tpu.amp import disable_casts

    x = jnp.ones((4, 4), jnp.float32)
    dot = lambda: jax.lax.dot_general(x, x, (((1,), (0,)), ((), ())))
    with _ctx(jnp.bfloat16):
        assert dot().dtype == jnp.bfloat16
        with disable_casts():
            assert dot().dtype == jnp.float32
        assert dot().dtype == jnp.bfloat16
    assert dot().dtype == jnp.float32


def test_cast_ops_nested_inside_disable_casts():
    """Entering cast_ops inside a disabled region must neither double-patch
    nor strip the outer region's wrappers on exit."""
    from apex_tpu.amp import disable_casts
    from apex_tpu.amp import cast_engine

    x = jnp.ones((4, 4), jnp.float32)
    dot = lambda: jax.lax.dot_general(x, x, (((1,), (0,)), ((), ())))
    with _ctx(jnp.bfloat16):
        n_saved = len(cast_engine._state.saved)
        with disable_casts():
            with _ctx(jnp.bfloat16):  # reentrant enter while disabled
                assert len(cast_engine._state.saved) == n_saved  # no re-patch
                assert dot().dtype == jnp.float32  # still disabled
        # outer region's wrappers intact and active again
        assert len(cast_engine._state.saved) == n_saved
        assert dot().dtype == jnp.bfloat16
    assert not cast_engine._state.saved
    assert dot().dtype == jnp.float32
