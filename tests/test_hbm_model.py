"""Analytic HBM ledger (apex_tpu.monitor.xray.hbm.model): digit pins.

The load-bearing contracts:

- BYTE EXACTNESS: every closed-form count is pinned against a
  hand-derived number (the test IS the derivation — a ledger that is
  "roughly right" cannot reconcile against ``memory_analysis()``);
- PARTITION IDENTITY: the predicted peak is DEFINED as the component
  sum, the identity survives a json round trip ``==``-for-``==``, and a
  breakdown whose declared peak disagrees with its components is
  rejected at parse;
- AGREEMENT WITH THE ALGEBRA: ``stash_depth`` duplicates (not imports)
  ``pipeline/algebra.schedule_cost``'s geometry validation so the
  ledger stays importable with jax absent — the two must accept and
  reject EXACTLY the same (schedule, P, M, V) tuples, and the schedule
  vocabularies must be equal;
- JAX-FREE: the whole predict path (model + oom forensics + kv-pool
  arithmetic) imports and computes with jax poisoned out of the
  interpreter — the feasibility oracle's any-box contract.
"""

import itertools
import json
import math
import os
import subprocess
import sys

import pytest

from apex_tpu.monitor.xray.hbm import model as hbm
from apex_tpu.monitor.xray.hbm.model import (
    Component,
    HbmBreakdown,
    TransformerDims,
    adam_state_bytes,
    distributed_adam_state_bytes,
    dtype_bytes,
    gpt_param_elements,
    kv_pool_bytes,
    predict_fits,
    predict_serving_memory,
    predict_train_memory,
    stash_depth,
    zero_padded_total,
    zero_shard_elements,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the dp2tp2 audit target's geometry (analysis/targets._tiny_cfg)
TINY = TransformerDims(
    num_layers=2, hidden_size=16, num_attention_heads=2,
    vocab_size=32, max_position_embeddings=8,
)


# ---------------------------------------------------------------------------
# dtype table


class TestDtypeBytes:
    def test_jax_and_hlo_spellings_agree(self):
        # the differ feeds parser dtypes (f32, bf16) straight in
        assert dtype_bytes("float32") == dtype_bytes("f32") == 4
        assert dtype_bytes("bfloat16") == dtype_bytes("bf16") == 2
        assert dtype_bytes("int8") == dtype_bytes("s8") == 1
        assert dtype_bytes("float8_e4m3fn") == 1

    def test_name_attribute_wins(self):
        class _D:
            name = "bfloat16"

        assert dtype_bytes(_D()) == 2

    def test_unknown_dtype_refused(self):
        with pytest.raises(ValueError, match="unknown dtype"):
            dtype_bytes("complex128")


# ---------------------------------------------------------------------------
# GPT parameter-element counts (the exact flax tree, leaf for leaf)


class TestGptParamElements:
    def test_tp2_pin(self):
        """Hand count at h=16, ffn=64, heads=2, V=32, P=8, tp=2:

        per layer: ln1 32 + qkv (16*24 + 24)=408 + attn-out
        (8*16 + 16)=144 + ln2 32 + h->ffn (16*32 + 32)=544 + ffn->h
        (32*16 + 16)=528  ->  1688.
        total: pos 8*16=128 + vocab-shard 16*16=256 + final-ln 32
        + 2*1688=3376  ->  3792.
        """
        assert gpt_param_elements(TINY, tp=2) == 3792

    def test_tp1_pin(self):
        # per layer: 32 + (16*48+48)=816 + (16*16+16)=272 + 32
        # + (16*64+64)=1088 + (64*16+16)=1040 -> 3280;
        # total: 128 + 32*16=512 + 32 + 2*3280=6560 -> 7232
        assert gpt_param_elements(TINY, tp=1) == 7232

    def test_tp_sharding_saves_exactly_the_sharded_kernels(self):
        # the delta tp=1 -> tp=2 is half of every column/row kernel +
        # column bias + the vocab shard; replicated leaves don't move
        assert gpt_param_elements(TINY, tp=1) > gpt_param_elements(TINY, tp=2)

    def test_indivisible_geometry_refused(self):
        with pytest.raises(ValueError, match="not divisible"):
            gpt_param_elements(TINY, tp=3)


# ---------------------------------------------------------------------------
# optimizer state


class TestOptimizerState:
    def test_fused_adam_pin(self):
        # 2 fp32 moment trees + int32 step scalar
        assert adam_state_bytes(3792) == 2 * 4 * 3792 + 4 == 30340

    def test_zero_flat_chunk_matches_multi_tensor(self):
        # the ledger MIRRORS the padding quantum (no import — jax-free);
        # this pin is the agreement contract
        from apex_tpu.ops import multi_tensor

        assert hbm.ZERO_FLAT_CHUNK == multi_tensor.CHUNK_SIZE == 65536

    def test_zero_padded_total_pins(self):
        # 7744 elements pad up to one 65536 chunk; 2 divides it
        assert zero_padded_total(7744, 2) == 65536
        # one element past a chunk boundary books a whole second chunk
        assert zero_padded_total(65537, 2) == 131072
        # minimum one chunk even for an empty tree
        assert zero_padded_total(0, 1) == 65536
        # the axis rounding is the SECOND padding (after the chunk pad)
        assert zero_padded_total(65536, 3) == 65538
        assert zero_shard_elements(65536, 3) == 21846

    def test_zero_padded_total_refuses_bad_geometry(self):
        with pytest.raises(ValueError):
            zero_padded_total(-1, 2)
        with pytest.raises(ValueError):
            zero_padded_total(10, 0)

    def test_distributed_adam_pin(self):
        """The gpt-pp ZeRO ground truth: 7744 f32 elements over 2 ranks
        -> 32768-element shards; 4 (step) + 32768*4 (fp32 master)
        + 2*32768*4 (moments) + 4 (ef scalar) = 393224."""
        assert distributed_adam_state_bytes(7744, 2) == 393224

    def test_param_remainders_halve_the_master_shard(self):
        # uint16 remainders: the bf16 param IS the high half
        base = distributed_adam_state_bytes(7744, 2)
        slim = distributed_adam_state_bytes(
            7744, 2, store_param_remainders=True
        )
        assert base - slim == 32768 * 2

    def test_error_feedback_books_a_full_residual_shard(self):
        base = distributed_adam_state_bytes(7744, 2)
        ef = distributed_adam_state_bytes(7744, 2, error_feedback=True)
        assert ef - base == 32768 * 4 - 4


# ---------------------------------------------------------------------------
# stash depths vs the schedule algebra (agreement, not import)


class TestStashDepth:
    def test_depth_pins(self):
        assert stash_depth("no_pipelining", 1, 4).activation_depth == 1
        assert stash_depth("no_pipelining", 1, 4).w_depth == 0
        # compiled two-scan 1f1b: all M stashes live at the boundary
        assert stash_depth("1f1b", 4, 8).activation_depth == 8
        assert stash_depth("1f1b", 4, 8).w_depth == 0
        # M per model chunk
        assert stash_depth("interleaved", 2, 4, 2).activation_depth == 8
        # zero-bubble's memory price: a second stash of deferred-W inputs
        zb = stash_depth("zero_bubble", 4, 8)
        assert (zb.activation_depth, zb.w_depth) == (8, 8)
        assert zb.total_depth == 16

    def test_schedule_vocabulary_matches_algebra(self):
        from apex_tpu.parallel.pipeline import algebra

        assert set(hbm.STASH_SCHEDULES) == set(algebra.SCHEDULES)

    @pytest.mark.parametrize(
        "schedule,p,m,v",
        [
            (s, p, m, v)
            for s in ("no_pipelining", "1f1b", "interleaved", "zero_bubble")
            for (p, m, v) in [
                (1, 1, 1), (2, 4, 1), (4, 8, 2), (2, 3, 2),
                (0, 4, 1), (2, 0, 1), (2, 4, 0), (3, 4, 2),
            ]
        ],
    )
    def test_geometry_agreement_with_algebra(self, schedule, p, m, v):
        """stash_depth duplicates schedule_cost's validation rather than
        importing it (the jax-free contract); this pin proves the two
        accept and reject exactly the same (schedule, P, M, V) tuples —
        including interleaved's V >= 2 and M % P == 0 rules."""
        from apex_tpu.parallel.pipeline import algebra

        def outcome(fn):
            try:
                fn()
                return "ok"
            except ValueError:
                return "rejected"

        ours = outcome(lambda: stash_depth(schedule, p, m, v))
        theirs = outcome(lambda: algebra.schedule_cost(schedule, p, m, v))
        assert ours == theirs, (
            f"stash_depth and schedule_cost disagree on "
            f"({schedule}, P={p}, M={m}, V={v}): {ours} vs {theirs}"
        )

    def test_unknown_schedule_refused(self):
        with pytest.raises(ValueError, match="no stash model"):
            stash_depth("gpipe", 2, 4)

    def test_activation_stash_pins(self):
        # remat="none": 10 stream-widths/token; 2 layers * 10 * 8 tokens
        # * 16 hidden * 2 B bf16 = 5120 (the dp2tp2 target's stash)
        kw = dict(compute_dtype="bfloat16")
        assert hbm.activation_stash_bytes(TINY, 8, remat="none", **kw) == 5120
        assert hbm.activation_stash_bytes(TINY, 8, remat="full", **kw) == 512
        assert (
            hbm.activation_stash_bytes(TINY, 8, remat="selective", **kw)
            == 1024
        )
        # schedule multiplies by the stash depth: 1f1b at M=4 holds 4
        assert hbm.activation_stash_bytes(
            TINY, 8, remat="full", schedule="1f1b",
            num_stages=2, num_microbatches=4, **kw
        ) == 4 * 512

    def test_unknown_remat_refused(self):
        with pytest.raises(ValueError, match="unknown remat"):
            hbm.activation_stash_bytes(TINY, 8, remat="magic")


# ---------------------------------------------------------------------------
# the breakdown partition identity


class TestBreakdown:
    def _bd(self, **kw):
        return HbmBreakdown(
            components=(
                Component("weights", 1000),
                Component("grads", 1000, transient=True),
                Component("optimizer_state", 2004),
            ),
            label="t", **kw,
        )

    def test_peak_is_defined_as_the_component_sum(self):
        bd = self._bd()
        assert bd.peak_bytes == 4004
        assert bd.resident_bytes == 3004
        assert bd.transient_bytes == 1000
        assert bd.resident_bytes + bd.transient_bytes == bd.peak_bytes

    def test_round_trip_preserves_identity_exactly(self):
        bd = self._bd(capacity_bytes=10_000)
        back = bd.round_trip()
        assert back == bd
        assert back.peak_bytes == bd.peak_bytes

    def test_from_dict_rejects_violated_identity(self):
        d = self._bd().to_dict()
        d["peak_bytes"] += 1
        with pytest.raises(ValueError, match="partition identity"):
            HbmBreakdown.from_dict(d)

    def test_duplicate_component_names_refused(self):
        with pytest.raises(ValueError, match="duplicate"):
            HbmBreakdown(
                components=(Component("w", 1), Component("w", 2))
            )

    def test_negative_bytes_refused(self):
        with pytest.raises(ValueError, match="negative"):
            Component("w", -1)

    def test_component_accessors(self):
        bd = self._bd()
        assert bd.component("weights").bytes == 1000
        assert bd.component("nope") is None
        assert bd.component_bytes("nope") == 0
        assert bd.headroom_bytes() is None
        assert self._bd(capacity_bytes=5000).headroom_bytes() == 996

    def test_with_components_extends(self):
        bd = self._bd().with_components(Component("kv_pool", 96))
        assert bd.peak_bytes == 4100
        assert bd.component_bytes("kv_pool") == 96


# ---------------------------------------------------------------------------
# the train-step prediction (the dp2tp2 target's exact table)


class TestPredictTrainMemory:
    def test_dp2tp2_component_pins(self):
        """The audit target's breakdown, digit for digit — the numbers
        the hlo-memory differ reconciles against ``memory_analysis()``
        in the gate (analysis/targets._gpt_hbm_prediction)."""
        bd = predict_train_memory(
            TINY, tp=2, microbatch_size=1, seq_len=8,
            optimizer="fused_adam", grad_scaler=True, remat="none",
            label="gpt-dp2tp2",
        )
        assert {c.name: c.bytes for c in bd.components} == {
            "weights": 15168,          # 3792 el x f32
            "grads": 15168,            # transient mirror
            "optimizer_state": 30340,  # 2*4*3792 + 4
            "scaler_state": 16,        # GradScaler: 4 scalars
            "batch_data": 64,          # 2 x (1x8) int32
            "activation_stash": 5120,  # remat=none: 2*10*8*16*2
        }
        assert bd.peak_bytes == 65876
        assert bd.transient_bytes == 15168 + 5120

    def test_matches_the_registered_audit_target(self):
        """ISSUE acceptance: the dp2tp2 GPT target's analytic sum equals
        the predicted peak digit-for-digit THROUGH a json round trip."""
        from apex_tpu.analysis.targets import dp2tp2_mesh, gpt_step_target

        tgt = gpt_step_target(dp2tp2_mesh())
        assert tgt.hbm is not None
        back = tgt.hbm.round_trip()
        assert back == tgt.hbm
        assert back.peak_bytes == sum(c.bytes for c in back.components)
        assert back.peak_bytes == 65876

    def test_zero_path_books_padded_shard_and_wire_buffer(self):
        bd = predict_train_memory(
            TINY, tp=2, microbatch_size=1, seq_len=8,
            optimizer="distributed_fused_adam", zero_axis_size=2,
            error_feedback=True, compression_wire_dtype="int8",
        )
        assert bd.component_bytes("optimizer_state") == (
            distributed_adam_state_bytes(3792, 2, error_feedback=True)
        )
        # one flat padded grad buffer at the wire dtype
        assert bd.component_bytes("compression_buffers") == (
            zero_padded_total(3792, 2) * 1
        )
        assert bd.component("compression_buffers").transient

    def test_distributed_needs_axis_size(self):
        with pytest.raises(ValueError, match="zero_axis_size"):
            predict_train_memory(
                TINY, seq_len=8, optimizer="distributed_fused_adam"
            )

    def test_unknown_optimizer_refused(self):
        with pytest.raises(ValueError, match="no optimizer-state model"):
            predict_train_memory(TINY, seq_len=8, optimizer="sgd")

    def test_no_scaler_no_component(self):
        bd = predict_train_memory(TINY, seq_len=8, grad_scaler=False)
        assert bd.component("scaler_state") is None


# ---------------------------------------------------------------------------
# the serving pool model vs CacheSpec.pool_shapes


class _Leaf:
    def __init__(self, shape, dtype="bfloat16"):
        self.shape, self.dtype = shape, dtype


class TestKvPool:
    def test_pin(self):
        # 2 layers x (K + V) x (4 blocks x 2 kv-heads x 8 slots x 8 hd)
        # x 2 B bf16
        assert kv_pool_bytes(
            num_layers=2, num_kv_heads=2, head_dim=8,
            num_blocks=4, block_size=8,
        ) == 2 * 2 * (4 * 2 * 8 * 8) * 2 == 4096

    def test_matches_cache_spec_pool_shapes(self):
        """The ledger's pool formula vs the REAL pool the engine
        allocates: sum of products over ``CacheSpec.pool_shapes``."""
        from apex_tpu.serving import kvcache

        shapes = {
            "transformer": {
                f"layers_{i}": {"attention": {
                    "cached_key": _Leaf((1, 4, 32, 8)),
                    "cached_value": _Leaf((1, 4, 32, 8)),
                    "cache_index": _Leaf(()),
                }}
                for i in range(3)
            }
        }
        spec = kvcache.CacheSpec.from_cache_shapes(shapes)
        pools = spec.pool_shapes(num_blocks=10, block_size=16)
        real = sum(
            math.prod(shape) * dtype_bytes(dtype)
            for shape, dtype in pools.values()
        )
        assert real == kv_pool_bytes(
            num_layers=3, num_kv_heads=4, head_dim=8,
            num_blocks=10, block_size=16, cache_dtype="bfloat16",
        )

    def test_predict_serving_memory(self):
        bd = predict_serving_memory(
            num_layers=2, num_kv_heads=2, head_dim=8,
            num_blocks=4, block_size=8, weights_bytes=1000,
            label="serve",
        )
        assert bd.component_bytes("kv_pool") == 4096
        assert bd.peak_bytes == 5096
        assert bd.round_trip() == bd


# ---------------------------------------------------------------------------
# the feasibility oracle


class TestPredictFits:
    def _bd(self, n):
        return HbmBreakdown(components=(Component("weights", n),))

    def test_exact_fit_at_zero_headroom(self):
        v = predict_fits(self._bd(100), 100)
        assert v.fits and v.headroom_bytes == 0 and v.utilization == 1.0

    def test_headroom_fraction_shrinks_the_budget(self):
        assert predict_fits(self._bd(91), 100).fits
        assert not predict_fits(self._bd(91), 100, 0.1).fits

    def test_verdict_is_serializable(self):
        v = predict_fits(self._bd(50), 200, 0.25)
        d = json.loads(json.dumps(v.to_dict()))
        assert d["fits"] is True and d["peak_bytes"] == 50

    def test_bad_inputs_refused(self):
        with pytest.raises(ValueError):
            predict_fits(self._bd(1), 0)
        with pytest.raises(ValueError):
            predict_fits(self._bd(1), 100, 1.0)


# ---------------------------------------------------------------------------
# the jax-free contract (the test_goodput subprocess convention)


_CHILD_PRELUDE = """
import sys
class _Poison:
    def find_module(self, name, path=None):
        if name in ("jax", "jaxlib", "flax"):
            raise ImportError("poisoned: " + name)
sys.meta_path.insert(0, _Poison())
import json
from apex_tpu.monitor.xray.hbm import model as hbm
from apex_tpu.monitor.xray.hbm import oom
from apex_tpu.monitor.xray.hbm.live import kv_pool_fields
"""


def _run_child(code, timeout=60):
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, "-c", _CHILD_PRELUDE + code],
        capture_output=True, text=True, env=env, timeout=timeout,
    )


class TestJaxFree:
    def test_predict_and_forensics_with_jax_poisoned(self):
        """The any-box contract: predict a breakdown, round-trip it,
        build + re-read an OOM incident, and compute KV-pool occupancy
        — all with jax UNIMPORTABLE (the feasibility oracle must run on
        the analysis box that has only the jsonl)."""
        code = """
dims = hbm.TransformerDims(
    num_layers=2, hidden_size=16, num_attention_heads=2,
    vocab_size=32, max_position_embeddings=8,
)
bd = hbm.predict_train_memory(
    dims, tp=2, microbatch_size=1, seq_len=8,
    optimizer="fused_adam", grad_scaler=True, remat="none",
)
assert bd.round_trip().peak_bytes == bd.peak_bytes == 65876

rec = oom_rec = oom.oom_record(
    7, RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
    breakdown=bd, capacity_bytes=1000,
)
lines = [json.dumps(rec), "", "not json", json.dumps({"kind": "metrics"})]
(inc,) = oom.read_oom_records(lines)
assert inc.step == 7
assert inc.dominant_component == "optimizer_state"
assert "--micro-batch" in inc.suggested_knobs()

kv = kv_pool_fields(num_blocks=8, free_blocks=2, block_size=4,
                    live_tokens=18)
assert kv["occupancy"] == 0.75 and kv["used_blocks"] == 6
assert abs(kv["fragmentation"] - 0.25) < 1e-9

fit = hbm.predict_fits(bd, 2 ** 20)
assert fit.fits

assert "jax" not in sys.modules
print("PEAK", bd.peak_bytes)
"""
        proc = _run_child(code)
        assert proc.returncode == 0, proc.stderr
        assert "PEAK 65876" in proc.stdout
