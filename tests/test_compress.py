"""Quantized gradient collectives (parallel/compress.py): quantization
core bounds, EF accumulation invariant, found_inf propagation, the
hand-counted compressed-bytes ledger pin on the dp2xtp2 GPT target, the
hlo-comms differ's positive int8-pattern confirmation, the defer_sync
relaxation, and the lint.compressed-collective home rule.

The acceptance spine (ISSUE 11): predicted dp-axis wire bytes drop
>= 3.5x vs the exact path, the differ CONFIRMS the int8 pattern was
emitted (zero new allowlist suppressions), and convergence/found_inf
parity is pinned by the slow-tier GPT example runs in
tests/test_examples.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from apex_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.monitor.xray import ledger as xlax
from apex_tpu.parallel import CompressionConfig, compress
from apex_tpu.parallel.ddp import all_reduce_gradients
from apex_tpu.parallel.utils import pcast_varying

DEVS = np.asarray(jax.devices())
pytestmark = pytest.mark.skipif(
    DEVS.size < 8, reason="needs the 8-device CPU mesh (conftest)"
)

CFG = CompressionConfig()


@pytest.fixture
def mesh():
    return Mesh(DEVS, ("dp",))


def _scale_exact(rng, shape, chunk):
    """Integer data that quantizes EXACTLY: every ``chunk``-aligned block
    carries a planted 254 (scale = 254/127 = 2) and even values, so
    ``round(x/2)*2 == x`` digit-for-digit in fp32."""
    x = (rng.randint(-126, 127, size=shape) * 2).astype(np.float32)
    flat = x.reshape(x.shape[0], -1) if x.ndim > 1 else x[None]
    flat[..., ::chunk] = 254.0
    return x


# ---------------------------------------------------------------------------
# quantization core


class TestQuantizeCore:
    def test_round_trip_error_bound(self):
        x = np.random.RandomState(0).randn(1000).astype(np.float32) * 3
        p, s = compress.quantize_blockwise(jnp.asarray(x), CFG)
        assert p.dtype == jnp.int8 and p.shape == (1000,)
        assert s.shape == (8,)  # ceil(1000/128)
        deq = np.asarray(compress.dequantize_blockwise(p, s, CFG))
        # per-element bound: half the block's scale
        bound = np.repeat(np.asarray(s), CFG.block_size)[:1000] / 2
        assert np.all(np.abs(deq - x) <= bound + 1e-7)

    def test_ragged_tail_and_zero_block(self):
        x = np.zeros(130, np.float32)
        x[:3] = [1.0, -2.0, 127.0]
        p, s = compress.quantize_blockwise(jnp.asarray(x), CFG)
        assert s.shape == (2,)
        deq = np.asarray(compress.dequantize_blockwise(p, s, CFG))
        np.testing.assert_array_equal(deq, x)  # scale-1 block + zero block

    @pytest.mark.parametrize("bad", [np.nan, np.inf])
    def test_nonfinite_block_poisons_whole_block_only(self, bad):
        x = np.ones(256, np.float32)
        x[5] = bad
        p, s = compress.quantize_blockwise(jnp.asarray(x), CFG)
        deq = np.asarray(compress.dequantize_blockwise(p, s, CFG))
        assert not np.isfinite(deq[:128]).any()   # poisoned block
        np.testing.assert_array_equal(deq[128:], x[128:])  # clean block

    def test_fp8_config(self):
        if "fp8" not in compress._WIRE_DTYPES:
            with pytest.raises(ValueError, match="not available"):
                CompressionConfig(dtype="fp8")
            return
        cfg = CompressionConfig(dtype="fp8")
        x = np.random.RandomState(1).randn(300).astype(np.float32)
        p, s = compress.quantize_blockwise(jnp.asarray(x), cfg)
        assert p.dtype == cfg.wire_dtype
        deq = np.asarray(compress.dequantize_blockwise(p, s, cfg))
        # e4m3 rounds to ~2^-4 RELATIVE error (3 mantissa bits), plus a
        # subnormal absolute floor near zero
        bound = np.abs(x) / 16 + np.repeat(
            np.asarray(s), cfg.block_size)[:300] / 32
        assert np.all(np.abs(deq - x) <= bound)

    def test_config_validation(self):
        with pytest.raises(ValueError, match="not available|choose"):
            CompressionConfig(dtype="int4")
        with pytest.raises(ValueError, match="block_size"):
            CompressionConfig(block_size=0)


# ---------------------------------------------------------------------------
# quantized collectives on the mesh


class TestQuantizedCollectives:
    def test_quantized_psum_tracks_exact(self, mesh):
        g = np.random.RandomState(1).randn(8, 500).astype(np.float32)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False,
        )
        def qsum(x):
            return compress.quantized_psum(x[0], "dp", CFG)

        got = np.asarray(qsum(g))
        exact = g.sum(0)
        # per-element error: 8 phase-1 block errors + 1 phase-2 error,
        # each bounded by the respective block amax / 254
        bound = (np.abs(g).max() * 8 + np.abs(exact).max()) / 254
        assert np.abs(got - exact).max() <= bound

    def test_scale_exact_data_is_exact(self, mesh):
        """All ranks IDENTICAL even-integer data with a planted 254 per
        chunk: phase 1 is exact by scale-2 design, and the phase-2
        reduced chunk is 8x the data — amax 8*254, scale 16, every
        element an exact multiple — so the whole decomposition is
        digit-for-digit equal to the psum."""
        row = _scale_exact(np.random.RandomState(2), (1, 512), 64)[0]
        g = np.broadcast_to(row, (8, 512)).copy()
        # chunk = 512/8 = 64 -> every rank-row block carries a 254

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False,
        )
        def qsum(x):
            return compress.quantized_psum(x[0], "dp", CFG)

        np.testing.assert_array_equal(np.asarray(qsum(g)), g.sum(0))

    def test_psum_scatter_phase1_exact_on_scale_exact_data(self, mesh):
        g = _scale_exact(np.random.RandomState(3), (8, 64), 8)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        )
        def qscat(x):
            return compress.quantized_psum_scatter(x[0], "dp", CFG)[None]

        got = np.asarray(qscat(g)).reshape(-1)
        np.testing.assert_array_equal(got, g.sum(0))

    def test_psum_scatter_rejects_indivisible(self, mesh):
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        )
        def qscat(x):
            return compress.quantized_psum_scatter(x[0], "dp", CFG)[None]

        with pytest.raises(ValueError, match="divisible"):
            jax.eval_shape(qscat, jnp.zeros((8, 63)))

    def test_quantized_all_gather(self, mesh):
        g = _scale_exact(np.random.RandomState(4), (8, 64), 64)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False,
        )
        def qgat(x):
            return compress.quantized_all_gather(x[0], "dp", CFG)

        np.testing.assert_array_equal(np.asarray(qgat(g)), g.reshape(-1))

    def test_quantized_all_gather_per_rank_scales(self, mesh):
        """Ranks with WILDLY different magnitudes: dequantization must
        apply each rank's OWN scales — a flat dequant of the gathered
        payload would read rank 0's scale across every shard (the
        misalignment quantized_psum's phase 2 also guards against)."""
        rng = np.random.RandomState(13)
        mags = 10.0 ** np.arange(8)  # 1 .. 1e7, one decade per rank
        g = (rng.rand(8, 64).astype(np.float32) + 0.5) * mags[:, None]

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False,
        )
        def qgat(x):
            return compress.quantized_all_gather(x[0], "dp", CFG)

        got = np.asarray(qgat(g)).reshape(8, 64)
        # per-rank relative error bounded by that rank's block scale
        for r in range(8):
            bound = np.abs(g[r]).max() / 254 + 1e-6
            assert np.abs(got[r] - g[r]).max() <= bound, r

    def test_min_elements_routes_small_leaves_exact(self, mesh):
        cfg = CompressionConfig(min_elements=32)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False,
        )
        def qsum(x):
            return compress.quantized_psum(x[0, :16], "dp", cfg)

        led = xlax.predict_comms(qsum, jnp.zeros((8, 16)))
        # below the threshold: ONE exact f32 psum, no quantized ops
        ops = {(e.op, e.dtype) for e in led.entries}
        assert ops == {("psum", "float32")}

    def test_checked_vma_mode_invariant_result(self, mesh):
        """Under jax's default CHECKED shard_map the gathered result must
        type invariant (out_specs P()) exactly like the psum it replaces
        — the _gather_tiled invariant-gather contract."""
        g = np.random.RandomState(5).randn(8, 256).astype(np.float32)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P(),
        )
        def qsum(x):
            x = x.reshape(x.shape[-1])
            # already dp-varying through in_specs: pcast_varying must
            # pass it through (a raw varying -> varying pcast is refused)
            x = pcast_varying(x, "dp")
            return compress.quantized_psum(x, "dp", CFG)

        got = np.asarray(qsum(g))
        exact = g.sum(0)
        bound = (np.abs(g).max() * 8 + np.abs(exact).max()) / 254
        assert np.abs(got - exact).max() <= bound


# ---------------------------------------------------------------------------
# error feedback


class TestErrorFeedback:
    def test_scatter_ef_invariant_digit_for_digit(self, mesh):
        """ACCEPTANCE (satellite): over T compressed reduce-scatters with
        error feedback, ``sum of applied updates + final residual ==
        sum of true grads`` DIGIT-FOR-DIGIT in fp32 on each rank — the
        telescoping identity e' = acc - C(acc). Data is scale-exact (even
        integers, planted 254 per chunk block) so every fp32 add/sub in
        the telescope is exact; residuals are genuinely nonzero on the
        way (odd intermediate sums quantize lossily)."""
        T, L = 4, 64  # chunk 8 per rank
        rng = np.random.RandomState(6)
        # per-rank grads: even ints with planted 254 -> scale 2 forever;
        # make them ODD sometimes via +1 so residuals become nonzero
        g_steps = []
        for _ in range(T):
            g = _scale_exact(rng, (8, L), 8)
            odd = (rng.rand(8, L) < 0.5) & (g != 254.0) & (np.abs(g) < 126)
            g = g + odd  # odd values: round(x/2)*2 != x -> residual ±1
            g_steps.append(g.astype(np.float32))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp"), P("dp")), check_vma=False,
        )
        def step(g, ef):
            acc = g[0] + ef[0]
            shard, sent = compress.quantized_psum_scatter(
                acc, "dp", CFG, return_transmitted=True
            )
            new_ef = compress.ef_update(acc, sent)
            return shard[None], sent[None], new_ef[None]

        ef = np.zeros((8, L), np.float32)
        sent_total = np.zeros((8, L), np.float32)
        any_resid = False
        for g in g_steps:
            shard, sent, ef = map(np.asarray, step(g, ef))
            sent_total += sent
            any_resid = any_resid or np.asarray(ef).any()
        true_total = sum(g_steps)
        # the per-rank telescope: transmitted + residual == true, exactly
        np.testing.assert_array_equal(sent_total + ef, true_total)
        assert any_resid  # the invariant was not vacuous

    def test_ddp_ef_bounds_accumulated_error(self, mesh):
        """With EF the CUMULATIVE applied-update error stays bounded by
        one step's quantization error instead of growing with T — the
        convergence mechanism the slow-tier parity tests rely on."""
        T, L = 8, 256
        rng = np.random.RandomState(7)
        g_steps = [rng.randn(8, L).astype(np.float32) for _ in range(T)]

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P("dp"), P("dp")),
            out_specs=(P(), P("dp")), check_vma=False,
        )
        def step(g, ef):
            out, new_ef = all_reduce_gradients(
                {"w": g[0]}, "dp", gradient_average=False,
                compression=CFG, ef_state={"w": ef[0]},
            )
            return out["w"], new_ef["w"][None]

        ef = np.zeros((8, L), np.float32)
        applied = np.zeros(L, np.float32)
        for g in g_steps:
            out, ef = step(g, ef)
            applied += np.asarray(out)
        true_total = sum(g.sum(0) for g in g_steps)
        # phase-1 errors telescope away; what remains is the CURRENT
        # residual + T phase-2 chunk errors (each bounded by amax/254)
        per_step_p2 = max(np.abs(g.sum(0)).max() for g in g_steps) / 254
        bound = np.abs(np.asarray(ef)).sum(0).max() + T * per_step_p2 + 1e-4
        assert np.abs(applied - true_total).max() <= bound
        # sanity: EF beats no-EF accumulation on the same stream
        ef0 = np.zeros((8, L), np.float32)
        applied_no_ef = np.zeros(L, np.float32)
        for g in g_steps:
            out, _ = step(g, ef0 * 0)  # residual always zero
            applied_no_ef += np.asarray(out)
        err_ef = np.abs(applied - true_total).mean()
        err_no = np.abs(applied_no_ef - true_total).mean()
        assert err_ef < err_no

    def test_nonfinite_grads_reach_found_inf_and_reset_residual(self, mesh):
        """ACCEPTANCE (satellite): overflow propagates through the
        compressed path to found_inf — and the residual for the
        poisoned leaf RESETS to zero instead of carrying NaN forever."""
        from apex_tpu.amp import GradScaler

        # no model-parallel axes on this dp-only test mesh; the found_inf
        # CONSENSUS psum itself stays on the exact path by construction
        scaler = GradScaler(loss_scale=128.0, model_parallel_axes=())

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P("dp"), P("dp")),
            out_specs=(P(), P(), P("dp")), check_vma=False,
        )
        def step(g, ef):
            out, new_ef = all_reduce_gradients(
                {"w": g[0]}, "dp", compression=CFG,
                ef_state={"w": ef[0]},
            )
            state = scaler.init()
            _, found_inf = scaler.unscale(state, out)
            return out["w"], found_inf, new_ef["w"][None]

        g = np.random.RandomState(8).randn(8, 256).astype(np.float32)
        ef = np.abs(np.random.RandomState(9).randn(8, 256)).astype(np.float32)
        _, found, _ = step(g, ef)
        assert not bool(found)
        g_bad = g.copy()
        g_bad[2, 7] = np.inf
        out, found, new_ef = step(g_bad, ef)
        assert bool(found)  # the poison crossed the compressed wire
        assert not np.isfinite(np.asarray(out)).all()
        # rank 2's residual covering the poisoned element reset to 0
        assert not np.asarray(new_ef)[2, :].any() or np.isfinite(
            np.asarray(new_ef)).all()

    def test_ef_requires_compression(self, mesh):
        with pytest.raises(ValueError, match="ef_state without"):
            all_reduce_gradients(
                {"w": jnp.zeros(4)}, "dp", ef_state={"w": jnp.zeros(4)}
            )


# ---------------------------------------------------------------------------
# ZeRO integration


class TestZeroCompressed:
    def _updates(self, mesh, compression, grads, params):
        from apex_tpu.optimizers import distributed_fused_adam

        opt = distributed_fused_adam(
            lr=1e-3, axis_name="dp", axis_size=8, average_grads=False,
            compression=compression,
        )

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False,
        )
        def one(p, g):
            st = opt.init(p)
            up, st2 = opt.update(g, st, p)
            return up, st2.ef_residual

        return one(params, grads)

    def test_compressed_update_tracks_exact_and_carries_residual(self, mesh):
        rng = np.random.RandomState(10)
        params = {"w": jnp.asarray(rng.randn(64, 8), jnp.float32)}
        grads = {"w": jnp.asarray(rng.randn(64, 8) * 1e-2, jnp.float32)}
        up_e, ef_e = self._updates(mesh, None, grads, params)
        up_c, ef_c = self._updates(mesh, CFG, grads, params)
        # exact path: scalar placeholder residual; compressed: real buffer
        assert np.asarray(ef_e).shape == ()
        assert np.asarray(ef_c).ndim == 1 and np.asarray(ef_c).any()
        # Adam normalizes the shard to ~±lr; quantization may move any
        # element by at most one lr
        assert float(jnp.max(jnp.abs(up_e["w"] - up_c["w"]))) <= 1e-3 + 1e-9

    def test_overflow_propagates_through_compressed_scatter(self, mesh):
        from apex_tpu.optimizers.distributed_fused_adam import (
            zero_scatter_grads,
        )

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        )
        def scat(g):
            shard, _, _ = zero_scatter_grads(
                {"w": g[0]}, "dp", 8, average=False, compression=CFG
            )
            return shard[None]

        g = np.random.RandomState(11).randn(8, 512).astype(np.float32)
        assert np.isfinite(np.asarray(scat(g))).all()
        g[4, 3] = np.nan
        assert not np.isfinite(np.asarray(scat(g))).all()


# ---------------------------------------------------------------------------
# the ledger pin + the three-referee acceptance


def _dp_totals(led):
    per = led.per_axis()
    return per.get("dp", {"bytes": 0, "ici_bytes": 0, "calls": 0})


class TestLedgerPin:
    """ACCEPTANCE: hand-counted compressed dp-axis bytes on the dp2xtp2
    GPT target, and the >= 3.5x predicted wire-byte drop vs exact."""

    @pytest.fixture(scope="class")
    def ledgers(self):
        from apex_tpu.analysis.targets import (
            dp2tp2_mesh, gpt_compressed_step_target, gpt_step_target,
        )

        mesh = dp2tp2_mesh()
        exact = gpt_step_target(mesh)
        comp = gpt_compressed_step_target(mesh)
        led_e = xlax.predict_comms(exact.fn, *exact.args)
        led_c = xlax.predict_comms(comp.fn, *comp.args)
        return exact, led_e, led_c

    def test_compressed_dp_bytes_hand_counted(self, ledgers):
        """payload + scales at their TRUE dtypes, digit for digit: per
        28-leaf grad tree, each leaf books the four quantized wire
        arrays (predicted_psum_wire_bytes is the documented formula),
        plus the one exact scalar loss pmean."""
        exact, led_e, led_c = ledgers
        n = 2  # dp axis size on the audit mesh
        leaf_sizes = [
            int(np.prod(l.shape, dtype=np.int64))
            for l in jax.tree_util.tree_leaves(exact.args[0])
        ]
        assert len(leaf_sizes) == 28 and sum(leaf_sizes) == 3792
        want_bytes = want_ici = 0
        for size in leaf_sizes:
            b, i = compress.predicted_psum_wire_bytes(size, n, CFG)
            want_bytes += b
            want_ici += i
        # + the scalar loss pmean (exact path, 4 B payload)
        want_bytes += 4
        want_ici += 4  # ceil(2*(n-1)*4/n) with n=2
        got = _dp_totals(led_c)
        assert got["bytes"] == want_bytes
        assert got["ici_bytes"] == want_ici
        # per-leaf op count: 2 all_to_all + 2 all_gather, + 1 pmean
        assert got["calls"] == 28 * 4 + 1
        # the wire dtypes are the TRUE payload dtypes
        dtypes = {e.dtype for e in led_c.entries if e.axis == "dp"}
        assert dtypes == {"int8", "float32"}

    def test_exact_dp_bytes_unchanged_and_drop_at_least_3_5x(self, ledgers):
        _, led_e, led_c = ledgers
        e, c = _dp_totals(led_e), _dp_totals(led_c)
        # the exact target's dp numbers: the PR-3 pin (28 f32 grad
        # psums + loss pmean)
        assert e["bytes"] == 3792 * 4 + 4
        drop = e["ici_bytes"] / c["ici_bytes"]
        assert drop >= 3.5, (e, c)
        # payload-bytes view drops too (all_to_all + gather double-count
        # the payload relative to one psum, so the floor is lower)
        assert e["bytes"] / c["bytes"] >= 2.0

    def test_timeline_join_reads_compressed_prediction(self, ledgers):
        """Mechanism pin for the third referee: the PR-6 bandwidth join
        consumes the COMPRESSED ledger — dp-axis predicted bytes in the
        join report drop by the same factor, so a hardware capture's
        measured seconds divide into achieved bytes/s against the true
        int8 wire bytes (benchmarks/run_all_tpu.py 'comms' section does
        the measuring)."""
        from apex_tpu.analysis.hlo import parse_hlo_module
        from apex_tpu.monitor.xray.timeline import analyze, parse_trace
        from test_timeline import (  # the synthetic-trace seam
            JOIN_HLO, dp2tp2_mesh as join_mesh, ev, step_marker, trace_dict,
        )

        _, led_e, led_c = ledgers
        tl = parse_trace(trace_dict(
            step_marker(0, 0.0, 1000.0),
            ev("all-reduce.1", 100.0, 200.0),  # a measured dp-axis event
        ))
        module = parse_hlo_module(JOIN_HLO)
        mesh = join_mesh()
        rep_e = analyze(tl, module=module, mesh=mesh, ledger=led_e)
        rep_c = analyze(tl, module=module, mesh=mesh, ledger=led_c)

        def dp(rep):
            return next(a for a in rep.axes if a.axis == "dp")

        # identical measured seconds, compressed predicted bytes: the
        # achieved-bytes/s denominator is the TRUE int8 wire bytes
        assert dp(rep_e).measured_us_per_step == 200.0
        assert dp(rep_c).measured_us_per_step == 200.0
        ratio = (dp(rep_e).predicted_ici_bytes_per_step
                 / dp(rep_c).predicted_ici_bytes_per_step)
        assert ratio >= 3.5
        assert (dp(rep_c).achieved_bytes_per_s
                < dp(rep_e).achieved_bytes_per_s)

    def test_differ_confirms_int8_pattern(self, ledgers):
        """ACCEPTANCE: the hlo-comms differ on the compressed target
        reports the quantized pattern MATCHED (comms.quantized, info)
        and nothing unpredicted/resharded/vanished — zero new allowlist
        suppressions needed."""
        from apex_tpu.analysis import StepContext
        from apex_tpu.analysis.hlo import audit_comms
        from apex_tpu.analysis.targets import (
            dp2tp2_mesh, gpt_compressed_step_target,
        )

        mesh = dp2tp2_mesh()
        tgt = gpt_compressed_step_target(mesh)
        ctx = StepContext(tgt)
        _, compiled = ctx.aot()
        fins = audit_comms(
            tgt.fn, *tgt.args, mesh=mesh,
            donate_argnums=tgt.donate_argnums, target=tgt.name,
            compiled=compiled,
        )
        assert all(f.severity == "info" for f in fins), [
            f.format() for f in fins
        ]
        (q,) = [f for f in fins if f.rule == "comms.quantized"]
        assert q.data["axis"] == "dp" and q.data["ops"] == 56
        # the only other finding is the known CSE fold (comms.folded),
        # identical to the exact target — no new suppressions
        others = {f.rule for f in fins} - {"comms.quantized"}
        assert others <= {"comms.folded"}


# ---------------------------------------------------------------------------
# defer_sync (arXiv:2506.19645 relaxation)


class TestDeferSync:
    def test_default_backward_reduce_scatters(self, mesh):
        from apex_tpu.parallel import mappings

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        )
        def f(x):
            return jax.grad(lambda x: (
                mappings.gather_from_sequence_parallel_region(x, "dp") ** 2
            ).sum())(x)

        led = xlax.predict_comms(f, jnp.zeros((8, 4)))
        assert "psum_scatter" in {e.op for e in led.entries}

    def test_defer_sync_skips_backward_collective(self, mesh):
        from apex_tpu.parallel import mappings

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        )
        def f(x):
            return jax.grad(lambda x: (
                mappings.gather_from_sequence_parallel_region(
                    x, "dp", True, True) ** 2
            ).sum())(x)

        led = xlax.predict_comms(f, jnp.zeros((8, 4)))
        # only the forward gather remains on the wire
        assert {e.op for e in led.entries} == {"all_gather"}
        # numerics: the local split of the exact cotangent
        x = np.random.RandomState(12).randn(8, 4).astype(np.float32)
        got = np.asarray(jax.jit(f)(x))
        np.testing.assert_allclose(got, 2 * x, rtol=1e-6)


# ---------------------------------------------------------------------------
# the home rule


class TestCompressedCollectiveLint:
    def test_seeded_composition_flagged(self):
        from apex_tpu.analysis.lint import run_lint

        files = {"apex_tpu/foo.py": (
            "def my_reduce(x, s):\n"
            "    q = quantize_blockwise(x)\n"
            "    return lax_psum(q)\n"  # not a collective name: clean
        )}
        assert run_lint(rules=["lint.compressed-collective"],
                        files=files) == []
        files = {"apex_tpu/foo.py": (
            "def my_reduce(x):\n"
            "    q, s = quantize_blockwise(x)\n"
            "    g = xlax.all_gather(q, 'dp')\n"
            "    return dequantize_blockwise(g, s)\n"
        )}
        (f,) = run_lint(rules=["lint.compressed-collective"], files=files)
        assert f.rule == "lint.compressed-collective"
        assert f.data == {"quant": "quantize_blockwise",
                          "collective": "all_gather",
                          "function": "my_reduce"}

    def test_wrapper_calls_not_flagged(self):
        from apex_tpu.analysis.lint import run_lint

        files = {"apex_tpu/bar.py": (
            "def reduce_grads(g, ef):\n"
            "    out = compress.quantized_psum(g, 'dp')\n"
            "    flag = xlax.psum(jnp.float32(0), 'tp')\n"
            "    return out, flag\n"
        )}
        assert run_lint(rules=["lint.compressed-collective"],
                        files=files) == []

    def test_compress_home_hits_and_is_allowlisted(self):
        from apex_tpu.analysis import REPO_ALLOWLIST
        from apex_tpu.analysis.lint import run_lint

        fins = run_lint(rules=["lint.compressed-collective"])
        assert fins, "the home rule must HIT compress.py (require_hit)"
        assert all("parallel/compress.py" in f.site for f in fins)
        result = REPO_ALLOWLIST.apply(fins, check_stale=False)
        assert result.ok and not result.findings
