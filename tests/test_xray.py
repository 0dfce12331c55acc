"""X-ray layer (apex_tpu.monitor.xray): collective-traffic ledger, XLA
memory reports, recompile sentinel.

The load-bearing contracts:

- BYTE EXACTNESS: ledger totals must match hand-computed values digit for
  digit (the per-op formulas are the documentation — a comms report that
  is "roughly right" cannot diff two runs);
- ZERO-COST PASSTHROUGH: the wrappers emit the exact same primitives, so
  numerics are bit-identical with and without an active ledger;
- the memory report gives a non-degenerate args/outputs/temps breakdown
  for a real jitted train step;
- a deliberately shape-polymorphic step triggers exactly ONE post-warmup
  recompile warning record.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.compat import shard_map
from apex_tpu.monitor import xray
from apex_tpu.monitor.xray import ledger as xlax
from jax.sharding import Mesh, PartitionSpec as P


def tp_mesh(n=2):
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


def f32b(*shape):
    """Bytes of an f32 array of this shape."""
    return int(np.prod(shape, dtype=np.int64)) * 4


class TestLedgerCore:
    def test_wrappers_are_passthrough(self):
        """Same numerics with and without an active ledger (the wrappers
        emit the identical primitive)."""
        mesh = tp_mesh(4)
        x = jnp.arange(16.0).reshape(4, 4)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("tp"), out_specs=P("tp"),
            check_vma=False,
        )
        def f(x):
            a = xlax.psum(x, "tp")
            b = xlax.all_gather(x, "tp", axis=0, tiled=True)
            c = xlax.psum_scatter(b, "tp", scatter_dimension=0, tiled=True)
            d = xlax.ppermute(x, "tp", [(i, (i + 1) % 4) for i in range(4)])
            return a + c + d

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("tp"), out_specs=P("tp"),
            check_vma=False,
        )
        def f_raw(x):
            a = jax.lax.psum(x, "tp")
            b = jax.lax.all_gather(x, "tp", axis=0, tiled=True)
            c = jax.lax.psum_scatter(b, "tp", scatter_dimension=0, tiled=True)
            d = jax.lax.ppermute(x, "tp", [(i, (i + 1) % 4) for i in range(4)])
            return a + c + d

        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(f_raw(x)))
        with xlax.comms_ledger() as led:
            y = jax.jit(f)(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(f_raw(x)))
        assert len(led.entries) == 4

    def test_nothing_recorded_without_context(self):
        mesh = tp_mesh(2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def f(x):
            return xlax.psum(x, "tp")

        with xlax.comms_ledger() as led:
            pass  # closed before any trace
        f(jnp.ones((2,)))
        assert led.entries == []

    def test_hand_counted_bytes_and_ici(self):
        """Every op's bytes/ici against the documented formulas, n=2:
        psum 2(n-1)/n*B = B; all_gather (n-1)*B = B; psum_scatter
        (n-1)/n*B = B/2; all_to_all (n-1)/n*B = B/2; ppermute B."""
        mesh = tp_mesh(2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(None, "tp"), out_specs=P(),
            check_vma=False,
        )
        def f(x):  # x local shard: (4, 4) f32 = 64 B
            g = xlax.all_gather(x, "tp", axis=1, tiled=True)  # 64 B in
            s = xlax.psum(g, "tp")                            # 128 B in
            r = xlax.psum_scatter(s, "tp", scatter_dimension=1, tiled=True)
            p = xlax.ppermute(r, "tp", [(0, 1)])              # 64 B
            a = xlax.all_to_all(
                jnp.broadcast_to(p[:, :, None], (4, 4, 2)), "tp",
                split_axis=2, concat_axis=2, tiled=True,
            )  # 128 B in
            m = xlax.pmax(jnp.sum(a), "tp")                   # 4 B
            return m

        led = xlax.predict_comms(f, jax.ShapeDtypeStruct((4, 8), jnp.float32))
        by_op = {e.op: e for e in led.entries}
        assert by_op["all_gather"].bytes == 64
        assert by_op["all_gather"].ici_bytes == 64
        assert by_op["psum"].bytes == 128
        assert by_op["psum"].ici_bytes == 128
        assert by_op["psum_scatter"].bytes == 128
        assert by_op["psum_scatter"].ici_bytes == 64
        assert by_op["ppermute"].bytes == 64
        assert by_op["ppermute"].ici_bytes == 64
        assert by_op["all_to_all"].bytes == 128
        assert by_op["all_to_all"].ici_bytes == 64
        assert by_op["pmax"].bytes == 4
        assert by_op["pmax"].ici_bytes == 4
        assert led.total_bytes(axis="tp") == 64 + 128 + 128 + 64 + 128 + 4
        assert set(led.per_axis()) == {"tp"}
        assert led.per_axis()["tp"]["axis_size"] == 2

    def test_axis_size_query_records_nothing(self):
        mesh = tp_mesh(2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def f(x):
            n = xlax.axis_size("tp")
            return x * n

        led = xlax.predict_comms(f, jnp.ones((3,)))
        assert led.entries == []

    def test_scaled_multiplier_and_muted(self):
        mesh = tp_mesh(2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def f(x):  # x: (4,) f32 = 16 B
            with xlax.scaled(5):
                a = xlax.psum(x, "tp")
            with xlax.muted():
                b = xlax.psum(x, "tp")  # probe: must not count
            return a + b

        led = xlax.predict_comms(f, jnp.ones((4,)))
        assert len(led.entries) == 1
        (e,) = led.entries
        assert e.count == 5 and e.bytes == 16 and e.total_bytes == 80
        assert led.total_bytes() == 80

    def test_predict_comms_sidesteps_jit_cache(self):
        """A compiled-and-cached step records nothing when CALLED, but
        predict_comms (eval_shape) still traces the wrappers."""
        mesh = tp_mesh(2)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def f(x):
            return xlax.psum(x, "tp")

        x = jnp.ones((4,))
        f(x)  # compile + cache
        with xlax.comms_ledger() as led_call:
            f(x)
        assert led_call.entries == []  # cache hit: no trace, no record
        led = xlax.predict_comms(f, x)
        assert len(led.entries) == 1 and led.total_bytes() == 16

    def test_to_records_schema_and_roofline(self, monkeypatch):
        mesh = tp_mesh(2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def f(x):
            return xlax.psum(x, "tp")

        monkeypatch.setenv("APEX_TPU_ICI_BANDWIDTH", "1e6")
        led = xlax.predict_comms(f, jnp.ones((250,)))  # 1000 B, ici 1000 B
        (rec,) = led.to_records(step=7)
        assert rec["kind"] == "comms" and rec["step"] == 7
        assert rec["axis"] == "tp" and rec["axis_size"] == 2
        assert rec["bytes"] == 1000 and rec["ici_bytes"] == 1000
        assert rec["ici_seconds"] == pytest.approx(1000 / 1e6)
        assert led.roofline_seconds() == {"tp": pytest.approx(1e-3)}
        # no bandwidth known (CPU, no env): None — never a fake number
        monkeypatch.delenv("APEX_TPU_ICI_BANDWIDTH")
        assert led.roofline_seconds() == {"tp": None}
        (rec2,) = led.to_records()
        assert rec2["ici_seconds"] is None

    def test_ici_bandwidth_table_and_override(self, monkeypatch):
        class FakeDev:
            device_kind = "TPU v5 lite"

        assert xlax.ici_bandwidth_per_device(FakeDev()) == 200e9
        FakeDev.device_kind = "TPU v6 lite"
        assert xlax.ici_bandwidth_per_device(FakeDev()) == 448e9
        FakeDev.device_kind = "cpu"
        assert xlax.ici_bandwidth_per_device(FakeDev()) is None
        monkeypatch.setenv("APEX_TPU_ICI_BANDWIDTH", "123.5e9")
        assert xlax.ici_bandwidth_per_device(FakeDev()) == 123.5e9

    def test_summary_mentions_axes_and_ops(self):
        mesh = tp_mesh(2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def f(x):
            return xlax.psum(x, "tp")

        led = xlax.predict_comms(f, jnp.ones((4,)))
        s = led.summary()
        assert "axis 'tp'" in s and "psum" in s
        assert xlax.CommsLedger().summary().startswith("comms ledger: no")


class TestTPMappingsComms:
    """Satellite: hand-counted byte totals for the mappings.py custom-vjp
    pairs in a TP forward+backward — gather fwd => reduce-scatter bwd,
    copy fwd (free) => psum bwd, etc. Because every pair's bwd is a
    custom_vjp rule (Python re-runs at trace time), a grad trace captures
    BOTH directions."""

    def test_tp_forward_backward_hand_counted(self):
        from apex_tpu.parallel import mappings

        mesh = tp_mesh(2)
        s, b, h = 8, 2, 4  # full sequence 8 -> local shard 4 under SP

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def step(x):  # x: (s, b, h) replicated
            def loss(x):
                # SP input shard -> gather fwd (all_gather of the local
                # (s/2, b, h)), reduce-scatter bwd (psum_scatter (s,b,h))
                xs = mappings.scatter_to_sequence_parallel_region(x)
                g = mappings.gather_from_sequence_parallel_region(
                    xs, to_model_parallel=True
                )
                # copy fwd (identity) => psum bwd of the (s, b, h) grad
                c = mappings.copy_to_tensor_model_parallel_region(g)
                # reduce fwd (psum (s, b, h)) => pcast bwd (no collective)
                r = mappings.reduce_from_tensor_model_parallel_region(c)
                return jnp.sum(r)

            l, g = jax.value_and_grad(loss)(x)
            return l

        led = xlax.predict_comms(
            step, jax.ShapeDtypeStruct((s, b, h), jnp.float32)
        )
        per_op = led.per_op(axis="tp")
        # all_gather x2: gather_from_sequence FWD gathers the local
        # (s/2, b, h) shard; scatter_to's BWD gathers the (s/2, b, h)
        # cotangent (via _typed_gather) — 128 B each here.
        assert per_op["all_gather"]["calls"] == 2
        assert per_op["all_gather"]["bytes"] == 2 * f32b(s // 2, b, h)
        # psum x2: reduce_from's FWD psum of (s, b, h) + copy_to's BWD
        # psum of the (s, b, h) grad (reduce_from's bwd is a pcast —
        # no collective).
        assert per_op["psum"]["calls"] == 2
        assert per_op["psum"]["bytes"] == 2 * f32b(s, b, h)
        # psum_scatter x1: gather_from_sequence(to_model_parallel=True)
        # BWD reduce-scatters the full (s, b, h) cotangent — the
        # "gather fwd => reduce-scatter bwd" pair of the SP head gather.
        assert per_op["psum_scatter"]["calls"] == 1
        assert per_op["psum_scatter"]["bytes"] == f32b(s, b, h)
        # the whole step moves exactly these five collectives
        assert sum(d["calls"] for d in per_op.values()) == 5
        assert set(per_op) == {"all_gather", "psum", "psum_scatter"}


class TestPipelineComms:
    """Satellite: one 1F1B pipeline step's ppermute traffic, hand-counted
    under compat.shard_map on the CPU mesh.

    The forward tick scan traces its body ONCE; schedules wrap it in
    ``xray.scaled(T)`` with T = M + P - 1, so the single traced edge
    weighs T executions. (The BACKWARD pipeline's edges come from jax's
    transpose of the scan — no Python, not recorded; they mirror forward
    one-for-one, as documented in the ledger module.)
    """

    PP = 4

    def test_1f1b_ppermute_traffic_hand_counted(self):
        from apex_tpu.parallel.pipeline import (
            forward_backward_pipelining_without_interleaving,
        )

        mesh = Mesh(np.array(jax.devices()[: self.PP]), ("pp",))
        M, micro_b, hid = 8, 2, 4

        def stage_fn(params, x):
            return jnp.tanh(x @ params)

        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False,
        )
        def step(params, mbs, targets):
            loss, _, _ = forward_backward_pipelining_without_interleaving(
                stage_fn, loss_fn, params, mbs, targets, axis_name="pp",
            )
            return loss

        led = xlax.predict_comms(
            step,
            jax.ShapeDtypeStruct((hid, hid), jnp.float32),
            jax.ShapeDtypeStruct((M, micro_b, hid), jnp.float32),
            jax.ShapeDtypeStruct((M, micro_b, hid), jnp.float32),
        )
        T = M + self.PP - 1
        act_bytes = f32b(micro_b, hid)  # one boundary activation
        # ONE traced ppermute edge, weighted by the T-tick scan
        assert led.total_bytes(op="ppermute", axis="pp") == T * act_bytes
        perms = led.filter(op="ppermute")
        assert len(perms) == 1 and perms[0].count == T
        # loss publication: psum of the per-microbatch losses (M,) plus
        # the scalar mean psum in _last_stage_mean_loss
        assert led.total_bytes(op="psum", axis="pp") == f32b(M) + f32b()
        assert set(led.per_axis()) == {"pp"}

    def test_tick_block_remat_weighs_padding_ticks(self):
        """Blocked remat pads the tick count to a block multiple — the
        padding ticks ship real edges and the ledger must count them."""
        from apex_tpu.parallel.pipeline import pipeline_forward

        mesh = Mesh(np.array(jax.devices()[: self.PP]), ("pp",))
        M, micro_b, hid, B = 6, 2, 4, 4  # T = 9 -> padded to 12

        def stage_fn(params, x):
            return jnp.tanh(x @ params)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False,
        )
        def fwd(params, mbs):
            outs = pipeline_forward(
                stage_fn, params, mbs, axis_name="pp", tick_block_remat=B
            )
            return jax.tree_util.tree_map(jnp.sum, outs)

        led = xlax.predict_comms(
            fwd,
            jax.ShapeDtypeStruct((hid, hid), jnp.float32),
            jax.ShapeDtypeStruct((M, micro_b, hid), jnp.float32),
        )
        T = M + self.PP - 1  # 9 useful ticks
        padded = -(-T // B) * B  # 12 executed ticks
        assert padded == 12
        assert led.total_bytes(op="ppermute") == padded * f32b(micro_b, hid)


class TestGPTStepComms:
    """ACCEPTANCE: a CPU-mesh GPT train step under the ledger produces
    per-axis byte totals matching hand-computed values exactly.

    Mesh dp=2 x tp=2. Collective inventory of the tiny GPT (tied
    embeddings, learned positions, no SP, fp32 compute), per step:

    tp axis (payload bytes, L layers, batch b, seq s, hidden h):
      forward:
        - VocabParallelEmbedding: reduce_from psum of (b, s, h)
        - per layer: RowParallel attn-out psum (s, b, h)
                   + RowParallel mlp-out psum (s, b, h)
        - vocab-parallel CE: pmax (b, s) + psum sum_exp (b, s)
                           + psum target-logit (b, s) + psum mean-logit (b, s)
      backward (custom_vjp rules):
        - per layer: copy_to bwd psum for the qkv input (s, b, h)
                   + copy_to bwd psum for the mlp input (s, b, h)
        - tied head attend: copy_to bwd psum of (s, b, h)
        - embedding reduce_from bwd: pcast only (no collective)
        - CE bwd: hand-written shard-local rule (no collective)
    dp axis:
        - all_reduce_gradients: one psum per param leaf (classic path
          under check_vma=False) = total param bytes
        - loss pmean: one f32 scalar
    """

    def test_gpt_step_per_axis_totals_exact(self):
        from apex_tpu.models import GPTModel, gpt_loss_fn
        from apex_tpu.parallel import parallel_state
        from apex_tpu.parallel.ddp import all_reduce_gradients
        from apex_tpu.transformer import TransformerConfig

        L, h, heads, vocab, s, b = 2, 8, 2, 32, 4, 2
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2
        )
        assert parallel_state.get_data_parallel_world_size() == 4
        cfg = TransformerConfig(
            num_layers=L,
            hidden_size=h,
            num_attention_heads=heads,
            vocab_size=vocab,
            max_position_embeddings=s,
            hidden_dropout=0.0,
            attention_dropout=0.0,
            sequence_parallel=False,
            compute_dtype=jnp.float32,
        )
        model = GPTModel(config=cfg)
        tokens = jnp.zeros((b, s), jnp.int32)

        @jax.jit  # un-jitted, a shard_map runs primitive by primitive
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def init(tokens):
            return model.init(jax.random.PRNGKey(0), tokens)

        params = init(tokens)
        param_bytes = sum(
            int(np.prod(l.shape, dtype=np.int64)) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(params)
        )

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False,
        )
        def train_step(p, tokens):
            def loss(p):
                return gpt_loss_fn(model.apply(p, tokens, labels=tokens))

            l, grads = jax.value_and_grad(loss)(p)
            all_reduce_gradients(grads, axis_name="dp")
            return xlax.pmean(l, "dp")

        led = xlax.predict_comms(train_step, params, tokens)

        f32 = 4
        hidden_psum = s * b * h * f32  # one (s, b, h)/(b, s, h) fp32 psum
        tok_stat = b * s * f32  # one per-token fp32 statistic
        expected_tp_psum = (
            hidden_psum          # embedding fwd reduce
            + 2 * L * hidden_psum  # per layer fwd: attn-out + mlp-out
            + 3 * tok_stat       # CE: sum_exp, target logit, mean logit
            + 2 * L * hidden_psum  # per layer bwd: qkv + mlp copy_to
            + hidden_psum        # tied head attend copy_to bwd
        )
        per_op_tp = led.per_op(axis="tp")
        assert per_op_tp["psum"]["bytes"] == expected_tp_psum
        assert per_op_tp["pmax"]["bytes"] == tok_stat
        assert set(per_op_tp) == {"psum", "pmax"}

        per_op_dp = led.per_op(axis="dp")
        assert per_op_dp["psum"]["bytes"] == param_bytes
        assert per_op_dp["pmean"]["bytes"] == f32
        assert set(per_op_dp) == {"psum", "pmean"}

        per_axis = led.per_axis()
        assert per_axis["tp"]["bytes"] == expected_tp_psum + tok_stat
        assert per_axis["dp"]["bytes"] == param_bytes + f32
        assert per_axis["tp"]["axis_size"] == 2
        assert per_axis["dp"]["axis_size"] == 4

    def test_records_route_through_router(self):
        """The comms records land in the shared jsonl-compatible stream
        with kind='comms'."""
        mesh = tp_mesh(2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def f(x):
            return xlax.psum(x, "tp")

        led = xlax.predict_comms(f, jnp.ones((4,)))
        mem = monitor.MemorySink()
        router = monitor.MetricRouter([mem])
        for rec in led.to_records(step=3):
            router.emit(rec)
        (got,) = mem.records
        assert got["kind"] == "comms" and got["step"] == 3
        assert got["bytes"] == 16


class TestMemoryReport:
    def test_non_degenerate_breakdown_for_train_step(self):
        """args/outputs/temps all nonzero for a jitted train-ish step
        (the acceptance bar: a real breakdown, not a row of zeros)."""

        def step(w, x):
            y = jnp.tanh(x @ w)
            loss = jnp.sum(y**2)
            g = jax.grad(lambda w: jnp.sum(jnp.tanh(x @ w) ** 2))(w)
            return loss, w - 0.1 * g

        w = jnp.ones((64, 64))
        x = jnp.ones((32, 64))
        rep = xray.memory_report(step, w, x)
        assert rep.argument_bytes > 0
        assert rep.output_bytes > 0
        assert rep.temp_bytes > 0
        assert rep.total_bytes >= (
            rep.argument_bytes + rep.output_bytes + rep.temp_bytes
            + rep.generated_code_bytes - rep.alias_bytes
        )
        # CPU reports no capacity: headroom is honestly None
        assert rep.device_memory_bytes is None
        assert rep.headroom_bytes is None
        fields = rep.fields()
        assert fields["temp_bytes"] == rep.temp_bytes
        assert "MiB" in rep.format()

    def test_accepts_prejitted_function(self):
        jitted = jax.jit(lambda x: (x @ x.T).sum())
        rep = xray.memory_report(jitted, jnp.ones((16, 16)))
        assert rep.argument_bytes == 16 * 16 * 4

    def test_headroom_math(self):
        rep = xray.MemoryReport(
            argument_bytes=100, output_bytes=50, temp_bytes=200,
            generated_code_bytes=25, alias_bytes=50,
            device_memory_bytes=1000,
        )
        assert rep.total_bytes == 325
        assert rep.headroom_bytes == 675
        assert "headroom" in rep.format()

    def test_bench_parity_with_direct_analysis(self):
        """The refactored pipeline-memory benchmark path must report the
        same temp bytes as the raw memory_analysis dance it replaced."""

        def f(x):
            return jnp.tanh(x @ x.T).sum()

        x = jnp.ones((32, 32))
        direct = (
            jax.jit(f).lower(x).compile().memory_analysis().temp_size_in_bytes
        )
        assert xray.memory_report(f, x).temp_bytes == direct


class TestCompileWatcher:
    def test_exactly_one_postwarmup_recompile_record(self):
        """ACCEPTANCE: a deliberately shape-polymorphic step triggers
        exactly one post-warmup recompile warning record."""
        mem = monitor.MemorySink()
        router = monitor.MetricRouter([mem])

        @jax.jit
        def step(x):
            return (x * 2.0 + 1.0).sum()

        watcher = xray.CompileWatcher(router=router)
        if not watcher.available:  # pragma: no cover - jax API drift
            pytest.skip("jax.monitoring not available")

        step(jnp.ones((8,)))  # warmup compile
        rec0 = watcher.on_step(0)
        assert rec0 is not None and rec0["recompile"] is False
        assert rec0["compiles"] >= 1 and rec0["compile_seconds"] > 0

        step(jnp.ones((8,)))  # cached: no compile
        assert watcher.on_step(1) is None

        step(jnp.ones((9,)))  # shape-polymorphic step: recompiles
        rec2 = watcher.on_step(2)
        assert rec2 is not None and rec2["recompile"] is True

        step(jnp.ones((9,)))  # warm again
        assert watcher.on_step(3) is None

        recompiles = [r for r in mem.records
                      if r["kind"] == "compile" and r["recompile"]]
        assert len(recompiles) == 1
        assert rec2["total_compiles"] > rec0["compiles"] - 1

    def test_standalone_records_without_router(self):
        @jax.jit
        def f(x):
            return x + 1

        watcher = xray.CompileWatcher()
        if not watcher.available:  # pragma: no cover
            pytest.skip("jax.monitoring not available")
        f(jnp.ones((3, 3)))
        rec = watcher.on_step(0)
        assert rec is not None and rec["kind"] == "compile"
        assert list(watcher.records) == [rec]  # bounded deque window
        assert watcher.records.maxlen == xray.CompileWatcher.MAX_RECORDS


class TestMoEFlops:
    """Satellite: num_experts/top-k-aware layer FLOPs, hand-counted."""

    def _cfg(self, **kw):
        from apex_tpu.transformer import TransformerConfig

        base = dict(
            num_layers=1, hidden_size=4, num_attention_heads=2,
            ffn_hidden_size=8, vocab_size=32, max_position_embeddings=8,
        )
        base.update(kw)
        return TransformerConfig(**base)

    def test_moe_layer_flops_hand_counted(self):
        h, ffn, s, E, k = 4, 8, 3, 4, 2
        cfg = self._cfg(num_moe_experts=E, moe_top_k=k)
        got = monitor.transformer_layer_flops_per_token(cfg, s)
        qkv = 2 * h * (3 * h)       # dense QKV (no GQA): 96
        attn = 2 * s * h + 2 * s * h  # scores + context: 48
        out = 2 * h * h             # 32
        router = 2 * h * E          # 32
        expert = 2 * h * ffn + 2 * ffn * h  # one ungated FFN pass: 128
        assert got == qkv + attn + out + router + k * expert

    def test_top1_moe_is_dense_plus_router(self):
        """Switch (top-1) runs exactly one expert per token: dense MLP
        FLOPs + the router matmul."""
        s = 5
        dense = monitor.transformer_layer_flops_per_token(self._cfg(), s)
        moe = monitor.transformer_layer_flops_per_token(
            self._cfg(num_moe_experts=4, moe_top_k=1), s
        )
        assert moe == dense + 2 * 4 * 4  # + 2*h*E router

    def test_top2_moe_mfu_would_be_understated_by_dense_count(self):
        """The bug this fixes: a top-2 MoE spends ~2x the dense MLP math;
        counting it as dense understates model FLOPs (overstates nothing
        — MFU computed from the dense count is simply wrong)."""
        s = 5
        cfg2 = self._cfg(num_moe_experts=8, moe_top_k=2)
        dense = monitor.transformer_layer_flops_per_token(self._cfg(), s)
        moe2 = monitor.transformer_layer_flops_per_token(cfg2, s)
        h, ffn = 4, 8
        assert moe2 - dense == 2 * h * 8 + (2 * h * ffn + 2 * ffn * h)

    def test_gpt_flops_compose_with_moe_layers(self):
        cfg = self._cfg(num_moe_experts=4, moe_top_k=2, num_layers=3)
        per_layer = monitor.transformer_layer_flops_per_token(cfg, 8)
        assert monitor.gpt_flops_per_token(cfg, 8) == (
            3 * per_layer + 2 * cfg.hidden_size * cfg.vocab_size
        )


class TestMemorySinkCap:
    def test_eviction_at_cap(self):
        sink = monitor.MemorySink(max_records=3)
        for i in range(5):
            sink.emit(monitor.make_record("metrics", i, i=i))
        assert len(sink.records) == 3
        assert [r["i"] for r in sink.records] == [2, 3, 4]  # oldest evicted

    def test_default_is_bounded(self):
        sink = monitor.MemorySink()
        assert sink.records.maxlen == monitor.MemorySink.DEFAULT_MAX_RECORDS

    def test_none_means_unbounded(self):
        sink = monitor.MemorySink(max_records=None)
        assert sink.records.maxlen is None

    def test_invalid_cap_rejected(self):
        with pytest.raises(ValueError):
            monitor.MemorySink(max_records=0)

    def test_router_integration_keeps_newest(self):
        sink = monitor.MemorySink(max_records=2)
        router = monitor.MetricRouter([sink])
        for i in range(4):
            router.metrics(i, loss=float(i))
        assert [r["step"] for r in sink.records] == [2, 3]
