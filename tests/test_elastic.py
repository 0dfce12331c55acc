"""Elastic restart: topology block, ZeRO regroup, resharded restore,
deadline-budgeted preemption saves, and the end-to-end chaos drill.

Fast tier: hand-built sharded state (device_put only — no shard_map
compiles) exercises the reshard/refusal/crc paths; the deadline decision
is a pure function of seeded EMAs + grace, pinned arm by arm; the
AutoResume integration drives real async saves on the 8-device CPU mesh.
Slow tier: ``python -m apex_tpu.resilience.elastic`` (the gate) and the
chaos drill through the real GPT example — SIGTERM at step k on 8
devices, resharded resume on 4 (and 4->8), loss trajectory pinned
against an uninterrupted run, goodput identity across both incarnations
under one run id.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import monitor
from apex_tpu.monitor import goodput
from apex_tpu.optimizers import zero_regroup_flat
from apex_tpu.resilience import integrity
from apex_tpu.resilience.elastic import (
    ElasticRestoreError,
    needs_reshard,
    restore_resharded,
    spec_from_json,
    spec_to_json,
    topology_block,
)
from apex_tpu.utils import AutoResume

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEVS = np.asarray(jax.devices())
pytestmark = pytest.mark.skipif(
    DEVS.size < 8, reason="needs the 8-device CPU mesh (conftest)"
)


def _mesh(n):
    return Mesh(DEVS[:n], ("dp",))


TOTAL = 225  # pad8 -> 232, pad4 -> 228: the dp change changes the length


def _padded(total, dp):
    return ((total + dp - 1) // dp) * dp


def _state(mesh, dp, seed=0, zeros=False):
    """Hand-built elastic-shaped state: replicated params + scalar +
    RNG key, one dp-sharded ZeRO-style flat buffer (padded to dp)."""
    rng = np.random.RandomState(seed)
    rep = NamedSharding(mesh, P())
    flat = np.zeros(_padded(TOTAL, dp), np.float32)
    if not zeros:
        flat[:TOTAL] = rng.randn(TOTAL)
    w = np.zeros((12, 16), np.float32) if zeros else rng.randn(12, 16)
    return {
        "params": {"w": jax.device_put(np.asarray(w, np.float32), rep)},
        "master": jax.device_put(flat, NamedSharding(mesh, P("dp"))),
        "rng": jax.device_put(np.asarray([3, 7], np.uint32), rep),
        "scale": jax.device_put(np.float32(512.0), rep),
    }


# ---------------------------------------------------------------------------
# topology block


class TestTopologyBlock:
    def test_block_records_layout(self):
        topo = topology_block(_state(_mesh(8), 8))
        assert topo["version"] == 1
        assert topo["mesh"] == {"axes": {"dp": 8}, "devices": 8}
        leaves = {l["path"]: l for l in topo["leaves"]}
        assert leaves["['params']['w']"]["shape"] == [12, 16]
        # a replicated leaf's P() serializes to the empty entry list
        assert leaves["['params']['w']"]["spec"] == []
        assert leaves["['params']['w']"]["zero_shard_axis"] is None
        m = leaves["['master']"]
        assert m["shape"] == [232] and m["dtype"] == "float32"
        assert m["spec"] == ["dp"]
        # the flat-shard marker: 1-D + sharded over exactly one axis
        assert m["zero_shard_axis"] == "dp"
        assert leaves["['rng']"]["dtype"] == "uint32"
        assert leaves["['scale']"]["shape"] == []

    def test_spec_json_round_trip(self):
        for spec in (P(), P("dp"), P(None, "tp"), P(("dp", "tp"), None)):
            assert spec_from_json(spec_to_json(spec)) == spec
        assert spec_from_json(None) == P()

    def test_host_arrays_read_replicated(self):
        topo = topology_block({"a": np.ones((3,), np.float32), "b": 2.0})
        assert topo["mesh"] is None
        assert all(l["spec"] is None and l["zero_shard_axis"] is None
                   for l in topo["leaves"])


# ---------------------------------------------------------------------------
# ZeRO flat-buffer regroup


class TestZeroRegroup:
    def test_truncate_drops_only_padding(self):
        arr = np.concatenate([np.arange(1, 6, dtype=np.float32),
                              np.zeros(3, np.float32)])
        out = zero_regroup_flat(arr, 6)
        assert out.shape == (6,)
        np.testing.assert_array_equal(out[:5], arr[:5])
        assert out[5] == 0

    def test_extend_pads_zeros(self):
        arr = np.arange(1, 5, dtype=np.float32)
        out = zero_regroup_flat(arr, 8)
        np.testing.assert_array_equal(out[:4], arr)
        assert not out[4:].any() and out.dtype == np.float32

    def test_identity_when_lengths_match(self):
        arr = np.arange(4, dtype=np.float32)
        np.testing.assert_array_equal(zero_regroup_flat(arr, 4), arr)

    def test_nonzero_truncation_refuses(self):
        arr = np.arange(1, 9, dtype=np.float32)  # no zero tail
        with pytest.raises(ValueError, match="state, not dp padding"):
            zero_regroup_flat(arr, 6)

    def test_non_1d_refuses(self):
        with pytest.raises(ValueError, match="1-D"):
            zero_regroup_flat(np.zeros((2, 2)), 2)


# ---------------------------------------------------------------------------
# resharded restore


class TestRestoreResharded:
    def test_8_to_4_regroups_and_lays_out_again(self, tmp_path):
        d = str(tmp_path)
        state8 = _state(_mesh(8), 8, seed=1)
        integrity.save_checkpoint_verified(d, 3, state8)
        target = _state(_mesh(4), 4, zeros=True)
        step, out = restore_resharded(d, target, mesh=_mesh(4))
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(out["params"]["w"]), np.asarray(state8["params"]["w"]))
        master = np.asarray(out["master"])
        assert master.shape == (228,)  # regrouped 232 -> 228
        np.testing.assert_array_equal(
            master[:TOTAL], np.asarray(state8["master"])[:TOTAL])
        assert not master[TOTAL:].any()
        # the new layout is REAL: dp-sharded on the 4-device mesh
        assert out["master"].sharding.spec == P("dp")
        assert dict(out["master"].sharding.mesh.shape) == {"dp": 4}
        np.testing.assert_array_equal(np.asarray(out["rng"]), [3, 7])
        assert float(out["scale"]) == 512.0

    def test_4_to_8_extends_padding(self, tmp_path):
        d = str(tmp_path)
        state4 = _state(_mesh(4), 4, seed=2)
        integrity.save_checkpoint_verified(d, 1, state4)
        step, out = restore_resharded(
            d, _state(_mesh(8), 8, zeros=True), mesh=_mesh(8))
        assert step == 1
        master = np.asarray(out["master"])
        assert master.shape == (232,)
        np.testing.assert_array_equal(
            master[:TOTAL], np.asarray(state4["master"])[:TOTAL])
        assert not master[TOTAL:].any()

    def test_needs_reshard_tri_state(self, tmp_path):
        d = str(tmp_path)
        assert needs_reshard(d, _mesh(8)) is None  # no checkpoint at all
        integrity.save_checkpoint_verified(d, 1, _state(_mesh(8), 8))
        assert needs_reshard(d, _mesh(8)) is False
        assert needs_reshard(d, _mesh(4)) is True
        # a newest manifest with no topology block is undecidable
        from apex_tpu.utils.checkpoint import save_checkpoint

        path = save_checkpoint(d, 2, _state(_mesh(8), 8))
        integrity.write_manifest(path)  # tree-less: no topology
        assert needs_reshard(d, _mesh(4)) is None

    def test_crc_mismatch_refuses(self, tmp_path):
        """File digests intact but the fingerprint disagrees with the
        restored bytes: the resharded restore must refuse, not ship."""
        d = str(tmp_path)
        integrity.save_checkpoint_verified(d, 1, _state(_mesh(8), 8))
        mpath = integrity.manifest_path(os.path.join(d, "step_1"))
        manifest = json.load(open(mpath))
        for leaf in manifest["fingerprint"]["leaves"]:
            if leaf["path"] == "['master']":
                leaf["crc32"] = (leaf["crc32"] + 1) & 0xFFFFFFFF
        json.dump(manifest, open(mpath, "w"))
        with pytest.raises(ElasticRestoreError, match="crc32 mismatch"):
            restore_resharded(d, _state(_mesh(4), 4, zeros=True),
                              mesh=_mesh(4))

    def test_refuses_non_zero_shape_change(self, tmp_path):
        d = str(tmp_path)
        integrity.save_checkpoint_verified(d, 1, _state(_mesh(8), 8))
        target = _state(_mesh(4), 4, zeros=True)
        target["params"]["w"] = jax.device_put(
            np.zeros((12, 17), np.float32), NamedSharding(_mesh(4), P()))
        with pytest.raises(ElasticRestoreError, match="refusing to guess"):
            restore_resharded(d, target, mesh=_mesh(4))

    def test_refuses_grown_flat_buffer(self, tmp_path):
        """The zero_shard_axis marker is a layout heuristic: a 1-D
        dp-sharded buffer whose target length GREW beyond what dp
        re-padding can explain (a resized table, not ZeRO padding) must
        refuse, not silently zero-extend."""
        d = str(tmp_path)
        integrity.save_checkpoint_verified(d, 1, _state(_mesh(8), 8))
        target = _state(_mesh(4), 4, zeros=True)
        target["master"] = jax.device_put(
            np.zeros(260, np.float32),  # 260 % 4 == 0, but no common T
            NamedSharding(_mesh(4), P("dp")))
        with pytest.raises(ElasticRestoreError,
                           match="migration, not a ZeRO regroup"):
            restore_resharded(d, target, mesh=_mesh(4))

    def test_refuses_dtype_change(self, tmp_path):
        d = str(tmp_path)
        integrity.save_checkpoint_verified(d, 1, _state(_mesh(8), 8))
        target = _state(_mesh(4), 4, zeros=True)
        target["scale"] = jax.device_put(
            np.float64(1.0).astype(np.float16),
            NamedSharding(_mesh(4), P()))
        with pytest.raises(ElasticRestoreError, match="dtype"):
            restore_resharded(d, target, mesh=_mesh(4))

    def test_refuses_absent_axis_and_bad_divisibility(self, tmp_path):
        d = str(tmp_path)
        integrity.save_checkpoint_verified(d, 1, _state(_mesh(8), 8))
        target = _state(_mesh(4), 4, zeros=True)
        specs = jax.tree_util.tree_map(lambda _: P(), target)
        specs["master"] = P("tp")
        with pytest.raises(ElasticRestoreError,
                           match="absent from the restore mesh"):
            restore_resharded(d, target, mesh=_mesh(4), target_specs=specs)
        # 12 x 16 'w' sharded over dp=8 on dim 1: 16 % 8 == 0 is fine,
        # but dim 0 (12) over dp=8 is not
        target8 = _state(_mesh(8), 8, zeros=True)
        specs8 = jax.tree_util.tree_map(lambda _: P(), target8)
        specs8["master"] = P("dp")
        specs8["params"] = {"w": P("dp", None)}
        with pytest.raises(ElasticRestoreError, match="not divisible"):
            restore_resharded(d, target8, mesh=_mesh(8), target_specs=specs8)


# ---------------------------------------------------------------------------
# error-feedback residual state (compressed collectives, PR "quantized
# gradient collectives"): marked advisory in the manifest, regrouped
# where the layout matches, reset-to-zero (never refused) otherwise


class TestErrorFeedbackReshard:
    """The satellite contract (ISSUE 11): EF leaves are marked ``ef`` in
    the topology block; across a topology change they regroup like ZeRO
    flat buffers when the length change is padding-only and otherwise
    reset to zero with a logged warning — a hard refusal is never the
    answer, EF state is advisory."""

    def _ef_state(self, mesh, dp, seed=0, zeros=False, ef_len=None,
                  ef_sharded=False):
        state = _state(mesh, dp, seed=seed, zeros=zeros)
        rng = np.random.RandomState(seed + 100)
        ef_len = _padded(TOTAL, dp) if ef_len is None else ef_len
        ef = np.zeros(ef_len, np.float32)
        if not zeros and ef_sharded:
            # per-rank residuals are nonzero EVERYWHERE (each rank's own
            # error) — truncation can never pass off as padding removal
            ef[:] = rng.randn(ef_len) * 1e-3
        elif not zeros:
            ef[:TOTAL] = rng.randn(TOTAL) * 1e-3
        spec = P("dp") if ef_sharded else P()
        state["ef_residual"] = jax.device_put(
            ef, NamedSharding(mesh, spec))
        return state

    def test_topology_block_marks_ef(self):
        topo = topology_block(self._ef_state(_mesh(8), 8))
        leaves = {l["path"]: l for l in topo["leaves"]}
        assert leaves["['ef_residual']"]["ef"] is True
        assert leaves["['master']"]["ef"] is False

    def test_8_to_4_regroups_padding_only_ef(self, tmp_path):
        """A replicated DDP-style flat residual (padding-only length
        change, zero tail) REGROUPS — the accumulated error survives."""
        d = str(tmp_path)
        state8 = self._ef_state(_mesh(8), 8, seed=3)
        # zero tail: only the padding region beyond TOTAL is zero
        integrity.save_checkpoint_verified(d, 1, state8)
        target = self._ef_state(_mesh(4), 4, zeros=True)
        step, out = restore_resharded(d, target, mesh=_mesh(4))
        assert step == 1
        ef = np.asarray(out["ef_residual"])
        assert ef.shape == (_padded(TOTAL, 4),)
        np.testing.assert_array_equal(
            ef[:TOTAL], np.asarray(state8["ef_residual"])[:TOTAL])

    def test_nonregroupable_ef_resets_to_zero_with_warning(self, tmp_path):
        """A dp-SHARDED per-rank residual concatenates over dp, so the
        global length change is NOT padding-only: reset to zero, warn,
        and restore everything else — never ElasticRestoreError."""
        import logging

        d = str(tmp_path)
        # sharded over dp=8: global length 8 * padded -> nonzero tail
        state8 = self._ef_state(_mesh(8), 8, seed=4, ef_len=8 * 232,
                                ef_sharded=True)
        np.asarray(state8["ef_residual"])  # materialize
        integrity.save_checkpoint_verified(d, 1, state8)
        target = self._ef_state(_mesh(4), 4, zeros=True, ef_len=4 * 228,
                                ef_sharded=True)
        # the elastic logger carries its own handlers; listen directly
        records = []
        handler = logging.Handler()
        handler.emit = records.append
        elog = logging.getLogger("apex_tpu.resilience.elastic")
        elog.addHandler(handler)
        try:
            step, out = restore_resharded(d, target, mesh=_mesh(4))
        finally:
            elog.removeHandler(handler)
        assert step == 1
        ef = np.asarray(out["ef_residual"])
        assert ef.shape == (4 * 228,) and not ef.any()
        assert any("resetting to zero" in r.getMessage() for r in records)
        # the REST of the state still restored with values
        np.testing.assert_array_equal(
            np.asarray(out["master"])[:TOTAL],
            np.asarray(state8["master"])[:TOTAL])

    def test_pre_compression_checkpoint_zero_fills_ef(self, tmp_path):
        """Migration shim: a checkpoint saved BEFORE the compressed
        collectives existed has no EF leaf at all — restoring it into a
        compression-enabled target zero-fills the advisory residual
        (with a warning) instead of refusing on the structure diff."""
        d = str(tmp_path)
        state8 = _state(_mesh(8), 8, seed=7)  # pre-upgrade: no ef leaf
        integrity.save_checkpoint_verified(d, 1, state8)
        target = self._ef_state(_mesh(4), 4, zeros=False)  # nonzero ef
        step, out = restore_resharded(d, target, mesh=_mesh(4))
        assert step == 1
        ef = np.asarray(out["ef_residual"])
        assert ef.shape == (_padded(TOTAL, 4),) and not ef.any()
        np.testing.assert_array_equal(
            np.asarray(out["master"])[:TOTAL],
            np.asarray(state8["master"])[:TOTAL])
        # a NON-advisory structure diff still refuses
        target2 = self._ef_state(_mesh(4), 4, zeros=True)
        target2["stray"] = jax.device_put(
            np.zeros(3, np.float32), NamedSharding(_mesh(4), P()))
        with pytest.raises(ElasticRestoreError, match="migration"):
            restore_resharded(d, target2, mesh=_mesh(4))

    def test_compression_off_drops_saved_ef_with_warning(self, tmp_path):
        """The reverse migration: a checkpoint saved WITH compression
        restores into a compression-off target — the checkpoint-only EF
        leaves are simply not restored (warning), everything else lands;
        and the ef marker is an EXACT segment match, so a leaf merely
        CONTAINING the name still refuses."""
        d = str(tmp_path)
        state8 = self._ef_state(_mesh(8), 8, seed=9)
        integrity.save_checkpoint_verified(d, 1, state8)
        target = _state(_mesh(4), 4, zeros=True)  # no ef leaf at all
        step, out = restore_resharded(d, target, mesh=_mesh(4))
        assert step == 1
        assert "ef_residual" not in out
        np.testing.assert_array_equal(
            np.asarray(out["master"])[:TOTAL],
            np.asarray(state8["master"])[:TOTAL])
        # near-miss name: NOT advisory -> structure diff refuses
        d2 = str(tmp_path / "near")
        state = _state(_mesh(8), 8, seed=10)
        state["chef_residual"] = jax.device_put(
            np.ones(4, np.float32), NamedSharding(_mesh(8), P()))
        topo = topology_block(state)
        assert all(not l["ef"] for l in topo["leaves"])
        integrity.save_checkpoint_verified(d2, 1, state)
        with pytest.raises(ElasticRestoreError, match="migration"):
            restore_resharded(d2, _state(_mesh(4), 4, zeros=True),
                              mesh=_mesh(4))

    def test_8_to_4_resume_with_compression_on(self, tmp_path):
        """ACCEPTANCE (satellite): a REAL compressed-ZeRO optimizer
        state — DistributedFusedAdamState with an error-feedback
        residual — saved on 8 devices resumes on 4: master/moments
        regroup via zero_shard_axis, the per-rank residual resets to
        zero (logged), nothing refuses."""
        import functools

        import jax.numpy as jnp
        from apex_tpu.compat import shard_map
        from apex_tpu.optimizers import (
            distributed_fused_adam, zero_state_specs,
        )
        from apex_tpu.parallel.compress import CompressionConfig

        cfg = CompressionConfig()
        d = str(tmp_path)
        params = {"w": np.arange(225, dtype=np.float32)}

        def make(mesh, dp):
            opt = distributed_fused_adam(
                lr=1e-3, axis_name="dp", axis_size=dp, compression=cfg)
            specs = zero_state_specs("dp", compression=cfg)
            rep = NamedSharding(mesh, P())
            init = functools.partial(
                shard_map, mesh=mesh, in_specs=(P(),), out_specs=specs,
                check_vma=False,
            )(opt.init)
            p = {"w": jax.device_put(jnp.asarray(params["w"]), rep)}
            return {"params": p, "opt": init(p)}

        state8 = make(_mesh(8), 8)
        # make the per-rank residual NONZERO (as after a real compressed
        # step) so the non-regroupable reset is observable: the global
        # view concatenates 8 per-rank buffers
        ef_global = np.asarray(state8["opt"].ef_residual)
        assert ef_global.ndim == 1 and ef_global.shape[0] % 8 == 0
        nonzero_ef = (np.random.RandomState(9)
                      .randn(ef_global.shape[0]).astype(np.float32) * 1e-3)
        state8["opt"] = state8["opt"]._replace(ef_residual=jax.device_put(
            nonzero_ef,
            NamedSharding(_mesh(8), P("dp"))))
        topo = topology_block(state8)
        leaves = {l["path"]: l for l in topo["leaves"]}
        assert leaves["['opt'].ef_residual"]["ef"] is True
        assert leaves["['opt'].ef_residual"]["spec"] == ["dp"]
        integrity.save_checkpoint_verified(d, 2, state8)

        target = make(_mesh(4), 4)
        step, out = restore_resharded(d, target, mesh=_mesh(4))
        assert step == 2
        # master/moments: the flat padded length is CHUNK_SIZE-dominated
        # here, so the global shape is dp-invariant and restores verbatim
        np.testing.assert_array_equal(
            np.asarray(out["opt"].master_shard),
            np.asarray(state8["opt"].master_shard))
        # the per-rank residual could not regroup (nonzero truncation):
        # reset to zero at the NEW dp's global length, not refused
        ef = np.asarray(out["opt"].ef_residual)
        assert ef.shape == (ef_global.shape[0] // 2,) and not ef.any()


# ---------------------------------------------------------------------------
# AutoResume integration: elastic routing + EMA persistence


class TestAutoResumeElastic:
    def test_restore_routes_through_resharder(self, tmp_path):
        d = str(tmp_path)
        ar8 = AutoResume(d, interval=1, install_handlers=False)
        state8 = _state(_mesh(8), 8, seed=5)
        ar8.step(1, state8)
        ar8.close()
        # the finalize folded a real measurement and persisted it
        manifest = integrity.read_manifest(os.path.join(d, "step_1"))
        assert manifest["autoresume"]["save_ema_s"] > 0
        assert manifest["topology"]["mesh"]["axes"] == {"dp": 8}

        ar4 = AutoResume(d, install_handlers=False)
        step0, out = ar4.restore(_state(_mesh(4), 4, zeros=True))
        assert step0 == 1
        master = np.asarray(out["master"])
        assert master.shape == (228,)
        np.testing.assert_array_equal(
            master[:TOTAL], np.asarray(state8["master"])[:TOTAL])
        # the restart inherited the previous incarnation's EMAs
        assert ar4._save_ema == manifest["autoresume"]["save_ema_s"]

    def test_same_mesh_restore_stays_on_normal_path(self, tmp_path):
        d = str(tmp_path)
        ar = AutoResume(d, interval=1, install_handlers=False)
        state = _state(_mesh(8), 8, seed=6)
        ar.step(1, state)
        ar.close()
        step0, out = AutoResume(d, install_handlers=False).restore(
            _state(_mesh(8), 8, zeros=True))
        assert step0 == 1
        np.testing.assert_array_equal(
            np.asarray(out["master"]), np.asarray(state["master"]))


# ---------------------------------------------------------------------------
# deadline-budgeted termination saves


def _tiny_state():
    rep = NamedSharding(_mesh(8), P())
    return {"w": jax.device_put(np.arange(8, dtype=np.float32), rep)}


class TestDeadlineDecision:
    """The decision is a pure function of grace/EMAs/pending — every arm
    pinned with seeded values (no IO)."""

    def _ar(self, tmp_path, **kw):
        return AutoResume(str(tmp_path), install_handlers=False, **kw)

    def test_no_budget_always_saves(self, tmp_path):
        ar = self._ar(tmp_path)
        ar._save_ema = 1e9
        decision, info = ar._emergency_decision()
        assert decision == "save" and info["grace_s"] is None

    def test_no_history_attempts_save(self, tmp_path):
        ar = self._ar(tmp_path, grace_s=0.001)
        decision, info = ar._emergency_decision()
        assert decision == "save" and info["save_ema_s"] is None

    def test_budget_covers_full_save(self, tmp_path):
        ar = self._ar(tmp_path, grace_s=100.0)
        ar._save_ema = 1.0
        ar.request_resume()  # anchors the countdown
        decision, info = ar._emergency_decision()
        assert decision == "save"
        assert info["remaining_s"] == pytest.approx(100.0, abs=1.0)

    def test_finalize_when_only_the_commit_fits(self, tmp_path):
        ar = self._ar(tmp_path, grace_s=1.0)
        ar._save_ema = 50.0
        ar._finalize_ema = 0.01
        ar._pending = {"step": 7, "fingerprint": None, "topology": None,
                       "issue_s": 0.0}
        ar.request_resume()
        decision, info = ar._emergency_decision()
        assert decision == "finalize" and info["pending_step"] == 7
        ar._pending = None  # avoid close() touching the fake

    def test_skip_when_nothing_fits(self, tmp_path):
        ar = self._ar(tmp_path, grace_s=0.001)
        ar._save_ema = 50.0
        ar._finalize_ema = 40.0
        ar._pending = {"step": 7, "fingerprint": None, "topology": None,
                       "issue_s": 0.0}
        ar.request_resume()
        decision, _ = ar._emergency_decision()
        assert decision == "skip"
        ar._pending = None

    def test_without_pending_tight_budget_still_skips(self, tmp_path):
        ar = self._ar(tmp_path, grace_s=0.001)
        ar._save_ema = 50.0
        ar.request_resume()
        assert ar._emergency_decision()[0] == "skip"

    def test_env_default_grace(self, tmp_path, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PREEMPTION_GRACE_S", "12.5")
        assert self._ar(tmp_path).grace_s == 12.5
        monkeypatch.setenv("APEX_TPU_PREEMPTION_GRACE_S", "nope")
        assert self._ar(tmp_path).grace_s is None


class TestDeadlineBehavior:
    """ACCEPTANCE: with a seeded grace budget smaller than the measured
    save EMA, AutoResume provably skips the fresh save and the restart
    restores the last VERIFIED step — no torn manifest ever treated as
    durable. Real saves, real manifests, 8-device mesh."""

    @pytest.fixture
    def router(self):
        sink = monitor.MemorySink()
        r = monitor.MetricRouter([sink])
        goodput.set_router(r)
        try:
            yield sink
        finally:
            goodput.set_router(None)
            r.close()

    def test_skip_abandons_pending_and_restores_last_verified(
            self, tmp_path, router):
        d = str(tmp_path)
        # background_finalize=False: the drill needs step 4's manifest
        # commit DETERMINISTICALLY un-landed when the SIGTERM decision
        # runs; with the default background verify a tiny state's commit
        # wins the race and there is nothing left to abandon (that
        # healthy outcome has its own pin in test_health.py)
        ar = AutoResume(d, interval=2, install_handlers=False,
                        background_finalize=False)
        s2, s4, s5 = (_state(_mesh(8), 8, seed=i) for i in (2, 4, 5))
        assert not ar.step(2, s2)        # interval save of step 2 (pending)
        assert not ar.step(3, s2)        # no-op step
        assert not ar.step(4, s4)        # finalizes step 2, pends step 4
        # seed: grace provably smaller than the measured save EMA
        assert ar._save_ema is not None and ar._save_ema > 0
        ar.grace_s = 1e-9
        ar.request_resume()
        assert ar.step(5, s5) is True
        assert ar.termination_decision == "skip"
        ar.close()
        # step 4's dir may exist (background write), but it is TOMBSTONED
        # — failed verification, not legacy-acceptable — and step 5 was
        # never written; the restart restores verified step 2
        ok, why = integrity.verify_checkpoint(os.path.join(d, "step_4"))
        assert not ok and "abandoned" in why
        assert not os.path.isdir(os.path.join(d, "step_5"))
        assert integrity.verified_latest_step(d) == 2
        step0, out = AutoResume(d, install_handlers=False).restore(
            _state(_mesh(8), 8, zeros=True))
        assert step0 == 2
        np.testing.assert_array_equal(
            np.asarray(out["master"]), np.asarray(s2["master"]))
        # the decision reached the goodput stream: a ckpt_save span slice
        # carrying it plus the preemption event with the inputs
        recs = list(router.records)
        (ev,) = [r for r in recs if r["kind"] == "preemption"]
        assert ev["decision"] == "skip" and ev["saved_step"] is None
        assert ev["grace_s"] == 1e-9 and ev["save_ema_s"] > 0
        assert ev["pending_step"] == 4
        spans = [r for r in recs if r["kind"] == "span"
                 and r.get("decision") == "skip"]
        assert spans and spans[0]["phase"] == "ckpt_save"

    def test_finalize_commits_pending_only(self, tmp_path, router):
        d = str(tmp_path)
        # background_finalize=False for the same determinism reason as
        # the skip drill above: the "finalize" arm needs a genuinely
        # pending step-4 commit at decision time
        ar = AutoResume(d, interval=2, install_handlers=False,
                        background_finalize=False)
        s2, s4, s5 = (_state(_mesh(8), 8, seed=i) for i in (2, 4, 5))
        assert not ar.step(2, s2)        # first save: calibration commit
        assert not ar.step(3, s2)
        assert not ar.step(4, s4)        # pending step 4 (overlapped)
        ar._save_ema = 50.0              # a fresh save "cannot" fit...
        ar._finalize_ema = 1e-6          # ...but the commit can
        ar.grace_s = 5.0
        ar.request_resume()
        assert ar.step(5, s5) is True
        assert ar.termination_decision == "finalize"
        ar.close()
        assert integrity.verified_latest_step(d) == 4
        assert not os.path.isdir(os.path.join(d, "step_5"))
        (ev,) = [r for r in router.records if r["kind"] == "preemption"]
        assert ev["decision"] == "finalize" and ev["saved_step"] == 4

    def test_default_save_decision_emits_event(self, tmp_path, router):
        d = str(tmp_path)
        ar = AutoResume(d, install_handlers=False)
        ar.request_resume()
        assert ar.step(1, _state(_mesh(8), 8)) is True
        assert ar.termination_decision == "save"
        ar.close()
        assert integrity.verified_latest_step(d) == 1
        (ev,) = [r for r in router.records if r["kind"] == "preemption"]
        assert ev["decision"] == "save" and ev["saved_step"] == 1


# ---------------------------------------------------------------------------
# retention: the torn-dir window pin lives in test_resilience.py


# ---------------------------------------------------------------------------
# the gate + the chaos drill (slow tier)


def test_elastic_selftest_gate(tmp_path):
    """The ``python -m apex_tpu.resilience.elastic`` gate exits 0 —
    8->4->8 round trips of a REAL ZeRO state plus every refusal case."""
    from apex_tpu.resilience.elastic.__main__ import main

    assert main(["--dir", str(tmp_path)]) == 0


def _run_gpt(args, devices, extra_env=None):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        **(extra_env or {}),
    )
    code = (
        "import jax; jax.config.update('jax_platforms','cpu')\n"
        f"import sys; sys.argv={['x'] + args!r}\n"
        f"exec(open('examples/gpt/pretrain_gpt.py').read())\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, env=env, timeout=600,
    )
    assert proc.returncode == 0, (
        f"pretrain_gpt failed rc={proc.returncode}\nstdout tail: "
        f"{proc.stdout[-1500:]}\nstderr tail: {proc.stderr[-1500:]}"
    )
    return proc.stdout


_DRILL_BASE = ["--layers", "2", "--hidden", "64", "--heads", "4",
               "--seq-len", "32", "--micro-batch", "1",
               "--global-batch", "16", "--log-interval", "1", "--zero"]


def _losses(jsonl_path):
    out = {}
    for line in open(jsonl_path):
        rec = json.loads(line)
        if rec.get("kind") == "metrics":
            out[rec["step"]] = rec["loss"]
    return out


@pytest.mark.chaos
def test_gpt_elastic_chaos_drill(tmp_path):
    """ACCEPTANCE, both directions: deterministic GPT+ZeRO run, SIGTERM
    at step k, restart on a different device count; params + dp-sharded
    ZeRO state + loss scale restore RESHARDED and verified, the loss
    trajectory continues within pinned tolerance of an uninterrupted
    run, and the goodput accountant books both incarnations under one
    run id with the partition identity exact."""
    steps = 8

    # the reference trajectory: uninterrupted 8-device run (the global
    # batch is dp-invariant, so it also references the 4-device runs)
    ref_jsonl = tmp_path / "ref.jsonl"
    _run_gpt(_DRILL_BASE + ["--steps", str(steps),
                            "--metrics-jsonl", str(ref_jsonl)], devices=8)
    ref = _losses(ref_jsonl)
    assert set(ref) == set(range(steps))

    for first_dev, second_dev, tag in ((8, 4, "8to4"), (4, 8, "4to8")):
        save = tmp_path / f"ck_{tag}"
        jsonl = tmp_path / f"m_{tag}.jsonl"
        out = _run_gpt(
            _DRILL_BASE + ["--steps", str(steps), "--save", str(save),
                           "--save-interval", "3",
                           "--chaos-sigterm-step", "4",
                           "--metrics-jsonl", str(jsonl)],
            devices=first_dev)
        assert "termination checkpoint at step 5; exiting" in out
        out = _run_gpt(
            _DRILL_BASE + ["--steps", str(steps), "--save", str(save),
                           "--save-interval", "3",
                           "--metrics-jsonl", str(jsonl)],
            devices=second_dev)
        assert "resumed from step 5" in out, out

        # the combined trajectory (incarnation 1 steps 0-4, incarnation 2
        # steps 5-7) matches the uninterrupted reference within tolerance
        got = _losses(jsonl)
        assert set(got) == set(range(steps))
        for s in range(steps):
            assert got[s] == pytest.approx(ref[s], abs=5e-2), (
                tag, s, got[s], ref[s])

        records = [json.loads(l) for l in open(jsonl)]
        # both incarnations announce themselves under ONE run id (the
        # --save anchor) and the second books real restore badput
        runs = [r for r in records if r["kind"] == "run"]
        assert len(runs) == 2
        assert len({r["run_id"] for r in runs}) == 1
        # the termination save emitted its deadline decision
        pre = [r for r in records if r["kind"] == "preemption"]
        assert pre and pre[0]["decision"] == "save"
        goodputs = [r for r in records if r["kind"] == "goodput"]
        assert len(goodputs) == 2
        assert goodputs[1]["badput_ckpt_restore_s"] > 0
        # replay the FULL two-incarnation stream offline: identity exact
        report = goodput.account(records, run_id=runs[0]["run_id"])
        f = report.fields()
        total = f["productive_s"]
        for phase in ("ckpt_save", "ckpt_restore", "rollback", "compile",
                      "data_wait", "stall", "init", "shutdown"):
            total = total + f[f"badput_{phase}_s"]
        assert total + f["unattributed_s"] == f["wall_s"]
        assert f["incarnations"] == 2
        assert f["badput_ckpt_save_s"] > 0


@pytest.mark.chaos
def test_gpt_preemption_skip_budget(tmp_path):
    """ACCEPTANCE: a grace budget provably smaller than the measured
    save EMA makes the termination SKIP the fresh save (and abandon the
    pending one); the restart restores the last VERIFIED step."""
    save = tmp_path / "ck"
    jsonl = tmp_path / "m.jsonl"
    # --no-background-finalize: the drill's assertions need step 4's
    # manifest commit DETERMINISTICALLY pending when the SIGTERM skip
    # decision runs; with the default background verify a tiny state's
    # commit can win the race and leave nothing to abandon (the healthy
    # outcome — pinned separately in test_health.py)
    out = _run_gpt(
        _DRILL_BASE + ["--steps", "8", "--save", str(save),
                       "--save-interval", "2",
                       "--chaos-sigterm-step", "5",
                       "--no-background-finalize",
                       "--metrics-jsonl", str(jsonl)],
        devices=8,
        extra_env={"APEX_TPU_PREEMPTION_GRACE_S": "0.000001"})
    # interval saves at 2 and 4 measured the EMA; at SIGTERM the pending
    # step-4 commit cannot fit either -> skip, and the example must NOT
    # claim a termination checkpoint
    assert "termination at step 6: skip (grace budget); exiting" in out, out
    assert "termination checkpoint" not in out
    records = [json.loads(l) for l in open(jsonl)]
    (ev,) = [r for r in records if r["kind"] == "preemption"]
    assert ev["decision"] == "skip" and ev["save_ema_s"] > 0
    # the newest VERIFIED step is the finalized interval save (step 2 —
    # step 4's manifest was never committed and is tombstoned)
    assert integrity.verified_latest_step(str(save)) == 2
    out = _run_gpt(
        _DRILL_BASE + ["--steps", "7", "--save", str(save),
                       "--save-interval", "100",
                       "--metrics-jsonl", str(jsonl)],
        devices=8)
    assert "resumed from step 2" in out, out
