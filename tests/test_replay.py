"""Deterministic replay & divergence forensics (apex_tpu.resilience.replay).

Fast tier: journal round trips, batch crc, chaos bit-flip mechanics,
journal diffing, the incident-bundle journal tail, and the AutoResume
anchor/flush wiring. Slow tier: the exit-nonzero selftest gate
(record -> replay -> inject-bitflip -> bisect on a tiny GPT target),
the cross-process determinism subprocess pin, and the ACCEPTANCE chaos
drill through the real GPT example (a single in-memory bit flip the
sentinel misses, pinned by ``replay --bisect`` to the exact step and
leaf; the clean control replays bitwise-identical).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# journal (jax-free)


class TestJournal:
    def _recorder(self, tmp_path, router=None):
        from apex_tpu.resilience.replay import FlightRecorder

        return FlightRecorder(str(tmp_path / "j.jsonl"), router=router)

    def test_round_trip(self, tmp_path):
        from apex_tpu.monitor import MemorySink, MetricRouter
        from apex_tpu.resilience.replay import load_journal

        mem = MemorySink()
        router = MetricRouter([mem])
        rec = self._recorder(tmp_path, router)
        rec.header("run-x", "gpt", config={"layers": 2}, devices=8,
                   platform="cpu")
        rec.anchor(0, init=True)
        rec.step(0, batch=[0, 16], batch_crc=123, loss=1.5, verdict=0,
                 layer_rms=np.asarray([0.5, 0.25], np.float32))
        rec.step(1, batch=[16, 32], batch_crc=456, loss=1.25, verdict=0)
        rec.anchor(2)
        rec.event(1, "bitflip_injected", path="['w']", bit=12)
        rec.close()

        j = load_journal(str(tmp_path / "j.jsonl"))
        assert j.header["target"] == "gpt"
        assert j.header["config"] == {"layers": 2}
        assert sorted(j.steps) == [0, 1]
        assert sorted(j.anchors) == [0, 2]
        assert j.anchors[0]["init"] is True
        assert j.steps[0]["layer_rms"] == [0.5, 0.25]
        assert j.steps[0]["loss"] == 1.5
        assert [e["event"] for e in j.events] == ["bitflip_injected"]
        # every record also reached the router as kind="journal"
        kinds = [r["kind"] for r in mem.records]
        assert kinds == ["journal"] * 6
        router.close()

    def test_float_fingerprints_round_trip_bitwise(self, tmp_path):
        """A float32 loss survives json EXACTLY (the bitwise-compare
        basis): widening to float64 is exact and repr round-trips."""
        from apex_tpu.resilience.replay import load_journal

        ugly = float(np.float32(1.0) / np.float32(3.0))
        rec = self._recorder(tmp_path)
        rec.header("r", "gpt")
        rec.step(0, loss=np.float32(1.0) / np.float32(3.0))
        rec.close()
        j = load_journal(str(tmp_path / "j.jsonl"))
        assert j.steps[0]["loss"] == ugly  # == , not isclose

    def test_last_wins_across_incarnations(self, tmp_path):
        from apex_tpu.resilience.replay import load_journal

        rec = self._recorder(tmp_path)
        rec.header("r", "gpt")
        rec.step(3, loss=1.0)
        rec.step(4, loss=2.0)
        rec.close()
        # restart: new header, step 3 re-executed from a restore
        from apex_tpu.resilience.replay import FlightRecorder

        rec2 = FlightRecorder(str(tmp_path / "j.jsonl"))
        rec2.header("r", "gpt")
        rec2.step(3, loss=9.0)
        rec2.close()
        j = load_journal(str(tmp_path / "j.jsonl"))
        assert len(j.headers) == 2
        assert j.steps[3]["loss"] == 9.0  # the newer incarnation wins
        assert j.steps[4]["loss"] == 2.0

    def test_torn_trailing_line_tolerated(self, tmp_path):
        from apex_tpu.resilience.replay import load_journal

        rec = self._recorder(tmp_path)
        rec.header("r", "gpt")
        rec.step(0, loss=1.0)
        rec.close()
        with open(tmp_path / "j.jsonl", "a") as f:
            f.write('{"kind": "journal", "event": "st')  # torn write
        j = load_journal(str(tmp_path / "j.jsonl"))
        assert sorted(j.steps) == [0]

    def test_journal_path_and_dir_loading(self, tmp_path):
        from apex_tpu.resilience.replay import journal_path, load_journal

        p = journal_path(str(tmp_path))
        assert p == str(tmp_path / "replay-journal.jsonl")
        from apex_tpu.resilience.replay import FlightRecorder

        rec = FlightRecorder(p)
        rec.header("r", "gpt")
        rec.close()
        # a checkpoint DIR is accepted and resolves to the sidecar
        assert load_journal(str(tmp_path)).header["target"] == "gpt"

    def test_breaks_in(self, tmp_path):
        from apex_tpu.resilience.replay import load_journal

        rec = self._recorder(tmp_path)
        rec.header("r", "gpt")
        rec.step(0, loss=1.0)
        rec.event(3, "rollback", to_step=2)
        rec.close()
        j = load_journal(str(tmp_path / "j.jsonl"))
        assert j.breaks_in(0, 5) and not j.breaks_in(3, 5)

    def test_needs_path_or_router(self):
        from apex_tpu.resilience.replay import FlightRecorder

        with pytest.raises(ValueError):
            FlightRecorder(None, router=None)

    def test_batch_crc(self):
        from apex_tpu.resilience.replay import batch_crc

        a = np.arange(64, dtype=np.int32)
        b = np.arange(64, dtype=np.int32)
        assert batch_crc(a) == batch_crc(b)
        assert batch_crc(a, b) != batch_crc(a)          # order/arity
        b[7] += 1
        assert batch_crc(a) != batch_crc(b)             # content
        # a non-contiguous view fingerprints its CONTENT, not its strides
        c = np.arange(128, dtype=np.int32)[::2]
        assert batch_crc(c) == batch_crc(np.ascontiguousarray(c))


# ---------------------------------------------------------------------------
# chaos bit flip


class TestBitflip:
    def _tree(self):
        import jax.numpy as jnp

        return {"w": jnp.ones((4, 4), jnp.float32),
                "b": jnp.zeros((3,), jnp.float32),
                "n": jnp.zeros((2,), jnp.int32)}

    def test_flips_exactly_one_bit(self):
        from apex_tpu.resilience import chaos

        tree = self._tree()
        flipped, info = chaos.bitflip_leaf(tree, bit=12, seed=0)
        # exactly one element of one leaf changed, by exactly one bit
        changed = []
        for (pa, a), (pb, b) in zip(
            _flat(tree), _flat(flipped)
        ):
            diff = np.asarray(a) != np.asarray(b)
            if diff.any():
                changed.append((pa, int(diff.sum())))
        assert changed == [(info["path"], 1)]
        before = np.float32(info["before"]).view(np.uint32)
        after = np.float32(info["after"]).view(np.uint32)
        assert bin(int(before ^ after)).count("1") == 1

    def test_deterministic_and_filtered(self):
        from apex_tpu.resilience import chaos

        tree = self._tree()
        _, i1 = chaos.bitflip_leaf(tree, seed=5)
        _, i2 = chaos.bitflip_leaf(tree, seed=5)
        assert i1 == i2
        _, i3 = chaos.bitflip_leaf(tree, seed=5, path_filter="['b']")
        assert "['b']" in i3["path"]
        with pytest.raises(ValueError):
            chaos.bitflip_leaf({"n": self._tree()["n"]})  # no float leaf

    def test_low_mantissa_bit_is_tiny(self):
        from apex_tpu.resilience import chaos

        _, info = chaos.bitflip_leaf(self._tree(), bit=12, seed=0)
        assert info["before"] != info["after"]
        assert abs(info["after"] - info["before"]) < 1e-3 * max(
            abs(info["before"]), 1.0
        )

    def test_faultplan_consumed_once(self):
        from apex_tpu.resilience import chaos

        plan = chaos.FaultPlan(bitflip_steps={3}, bitflip_seed=1)
        tree = self._tree()
        t1, info = plan.maybe_bitflip(2, tree)
        assert info is None and t1 is tree
        t2, info = plan.maybe_bitflip(3, tree)
        assert info is not None
        t3, info = plan.maybe_bitflip(3, t2)
        assert info is None and t3 is t2  # fired once

    def test_sharding_preserved(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from apex_tpu.resilience import chaos

        mesh = Mesh(np.asarray(jax.devices())[:4], ("dp",))
        sh = NamedSharding(mesh, P("dp"))
        tree = {"w": jax.device_put(np.ones((8, 2), np.float32), sh)}
        flipped, _ = chaos.bitflip_leaf(tree, seed=0)
        assert flipped["w"].sharding == sh


def _flat(tree):
    import jax

    return [(jax.tree_util.keystr(p), l)
            for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]]


# ---------------------------------------------------------------------------
# determinism guard + journal diff


class TestGuardAndDiff:
    def test_determinism_guard_pins_and_reports(self):
        import jax

        from apex_tpu.resilience.replay import determinism_guard

        flags = determinism_guard()
        assert flags["matmul_precision"] == "highest"
        assert flags["x64"] is False
        assert flags["platform"] == jax.default_backend()
        # the replaying side applies the HEADER's flags, not defaults —
        # including a recorded unpinned None precision (the examples'
        # journaling-on-by-default mode must not alter run numerics)
        flags2 = determinism_guard({"matmul_precision": None,
                                    "x64": False})
        assert flags2["matmul_precision"] is None
        # pin=False records without mutating: the flag stays whatever
        # the header application above left it at
        flags3 = determinism_guard(pin=False)
        assert flags3["matmul_precision"] is None
        # restore the conftest default for later tests in this process
        jax.config.update("jax_default_matmul_precision", None)

    def _journal(self, records):
        from apex_tpu.resilience.replay import Journal

        base = [{"kind": "journal", "event": "header", "step": 0,
                 "target": "llama-scan"}]
        return Journal(base + records)

    def _step(self, s, **f):
        return {"kind": "journal", "event": "step", "step": s, **f}

    def test_identical_journals_diff_clean(self):
        from apex_tpu.resilience.replay import compare_journals

        a = self._journal([self._step(0, loss=1.5), self._step(1, loss=1.2)])
        rep = compare_journals(a, a)
        assert rep.ok and rep.steps_replayed == 2

    def test_diff_flags_first_divergent_step(self):
        from apex_tpu.resilience.replay import compare_journals

        a = self._journal([self._step(0, loss=1.5), self._step(1, loss=1.2)])
        b = self._journal([self._step(0, loss=1.5),
                           self._step(1, loss=1.2000001)])
        rep = compare_journals(a, b)
        assert not rep.ok and rep.first_divergent_step == 1

    def test_diff_localizes_layer(self):
        from apex_tpu.resilience.replay import compare_journals

        a = self._journal([self._step(0, layer_rms=[0.5, 0.25, 0.125])])
        b = self._journal([self._step(0, layer_rms=[0.5, 0.25001, 0.13])])
        rep = compare_journals(a, b)
        (d,) = rep.divergences
        assert d["first_divergent_layer"] == 1
        assert d["divergent_layers"] == [1, 2]

    def test_nan_agrees_with_nan(self):
        from apex_tpu.resilience.replay import compare_journals

        a = self._journal([self._step(0, loss=float("nan"))])
        assert compare_journals(a, a).ok


# ---------------------------------------------------------------------------
# incident bundle carries the journal tail


class TestIncidentJournalTail:
    def test_bundle_includes_journal_tail(self):
        from apex_tpu.monitor.router import MemorySink, make_record
        from apex_tpu.resilience.health import capture_incident

        window = MemorySink()
        window.emit(make_record("metrics", 1, loss=1.0))
        window.emit(make_record("journal", 1, event="step", loss=1.0))
        window.emit(make_record("journal", 2, event="anchor"))
        rec = capture_incident(None, step=2, window=window)
        assert [r["event"] for r in rec["journal_tail"]] == [
            "step", "anchor"
        ]
        # the journal records ALSO stay in the full record tail
        assert any(r["kind"] == "journal" for r in rec["record_tail"])


# ---------------------------------------------------------------------------
# AutoResume anchor/flush wiring


class _JournalStub:
    def __init__(self):
        self.anchors = []
        self.events = []
        self.flushes = 0

    def anchor(self, step, **f):
        self.anchors.append(step)

    def event(self, step, event, **f):
        self.events.append((step, event))

    def flush(self):
        self.flushes += 1


class TestAutoResumeJournal:
    def test_save_anchors_and_commit_flushes(self, tmp_path):
        import jax.numpy as jnp

        from apex_tpu.utils import AutoResume

        stub = _JournalStub()
        ar = AutoResume(str(tmp_path), interval=1, install_handlers=False,
                        journal=stub)
        state = {"w": jnp.ones((4,), jnp.float32)}
        ar.step(1, state)
        ar.finalize()
        assert stub.anchors == [1]
        assert stub.flushes >= 1  # the manifest commit made it durable
        ar.close()

    def test_incident_exit_flushes_even_with_nothing_pending(self, tmp_path):
        from apex_tpu.utils import AutoResume

        stub = _JournalStub()
        ar = AutoResume(str(tmp_path), install_handlers=False, journal=stub)
        assert ar.prepare_incident_exit() is None
        assert stub.flushes == 1
        ar.close()

    def test_abandon_notes_the_anchor(self, tmp_path):
        import jax.numpy as jnp

        from apex_tpu.utils import AutoResume

        stub = _JournalStub()
        ar = AutoResume(str(tmp_path), interval=1, install_handlers=False,
                        journal=stub, background_finalize=False)
        # issue an async save but don't finalize; then abandon it
        ar._save(2, {"w": jnp.ones((4,), jnp.float32)}, durable=False)
        # first save is a calibration (finalizes immediately) — issue a
        # second to leave a genuinely pending one
        ar._save(3, {"w": jnp.ones((4,), jnp.float32)}, durable=False)
        if ar._pending is not None:
            ar._abandon_pending()
            assert (3, "anchor_abandoned") in stub.events
            assert stub.flushes >= 1
        ar.close()


# ---------------------------------------------------------------------------
# the gate + the subprocess pins (slow tier)


def test_replay_selftest_gate(tmp_path):
    """``python -m apex_tpu.resilience.replay --selftest`` exits 0:
    record -> bitwise replay -> inject-bitflip -> bisect pins the exact
    step and leaf on a tiny GPT target."""
    from apex_tpu.resilience.replay.__main__ import main

    assert main(["--selftest", "--dir", str(tmp_path)]) == 0


_DETERMINISM_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from apex_tpu.data import IndexedTokenDataset, LMDataset
from apex_tpu.resilience.replay.replayer import determinism_guard
from apex_tpu.resilience.replay.targets import synthetic_corpus
from apex_tpu.training import GPTTargetConfig, build_gpt_training

determinism_guard()
cfg = GPTTargetConfig(vocab=64, seq_len=16, layers=2, hidden=32, heads=4,
                      tp=1, micro_batch=1, global_batch=8, spike_warmup=4)
corpus = sys.argv[1]
training = build_gpt_training(cfg)
lm = LMDataset(IndexedTokenDataset(corpus), seq_len=cfg.seq_len)
state = training.init_state()
bag = training.init_bag()
import jax.numpy as jnp
fingerprints = []
for step in range(5):
    ids = list(range(step * cfg.global_batch, (step + 1) * cfg.global_batch))
    x, y = training.reshape_batch(*lm.batch(ids))
    out = training.train_step(*state, bag, jnp.asarray(x), jnp.asarray(y),
                              jnp.asarray(0.0, jnp.float32),
                              jnp.asarray(1.0, jnp.float32))
    (*state, bag, loss, verdict) = out
    state = tuple(state)
    fingerprints.append([float(np.asarray(loss)), int(np.asarray(verdict))])
from apex_tpu.resilience import integrity
fp = integrity.tree_fingerprint(state)
print("FINGERPRINTS " + json.dumps(
    {"steps": fingerprints, "state": fp["structure_hash"],
     "crcs": [l["crc32"] for l in fp["leaves"]]}))
"""


def test_cross_process_determinism(tmp_path):
    """Two FRESH processes running the same journaled 5-step CPU segment
    produce bitwise-identical per-step fingerprints AND per-leaf state
    crc32s — the foundation the replay referee stands on, pinned with
    the blessed ``determinism_guard`` the CLI and recorder share."""
    # one shared corpus so the pin isolates the COMPUTE, not the data gen
    from apex_tpu.resilience.replay.targets import synthetic_corpus

    corpus = synthetic_corpus(64, n_tokens=4_000)
    results = []
    for _ in range(2):
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        proc = subprocess.run(
            [sys.executable, "-c", _DETERMINISM_CHILD, corpus],
            capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
        )
        assert proc.returncode == 0, (
            f"child failed\nstdout: {proc.stdout[-1500:]}\n"
            f"stderr: {proc.stderr[-1500:]}"
        )
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("FINGERPRINTS ")][0]
        results.append(json.loads(line[len("FINGERPRINTS "):]))
    assert results[0] == results[1]  # bitwise: == on exact json values


# ---------------------------------------------------------------------------
# ACCEPTANCE: the chaos drill through the real GPT example (slow tier)


def _run_gpt(args, devices=8):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
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


_DRILL = ["--steps", "8", "--layers", "2", "--hidden", "64", "--heads", "4",
          "--seq-len", "32", "--micro-batch", "1", "--global-batch", "16",
          "--log-interval", "2", "--save-interval", "2"]


@pytest.mark.chaos
def test_gpt_replay_bitflip_drill(tmp_path):
    """ACCEPTANCE (ISSUE 12): a single in-memory bit flip injected into
    the params at step 3 of a GPT run passes the sentinel and the run
    completes — but ``replay --bisect`` from the journal + checkpoint
    dir identifies the step and the exact flipped leaf. The clean-run
    control replays bitwise-identical with zero divergence records."""
    from apex_tpu.resilience.replay import load_journal
    from apex_tpu.resilience.replay.__main__ import main as replay_main

    clean = str(tmp_path / "clean")
    flip = str(tmp_path / "flip")
    out_clean = _run_gpt(_DRILL + ["--save", clean])
    out_flip = _run_gpt(
        _DRILL + ["--save", flip, "--chaos-bitflip-step", "3"]
    )
    assert "[chaos] bit-flipped" in out_flip

    # the sentinel MISSED it: no anomalies, no skips, the run completed
    fj = load_journal(flip)
    assert all(r.get("verdict") == 0 for r in fj.steps.values())
    assert "anomalies this run" not in out_flip
    (flip_event,) = [e for e in fj.events
                     if e["event"] == "bitflip_injected"]
    assert flip_event["step"] == 3

    # clean control: bitwise-identical replay, zero divergence (exit 0)
    assert replay_main([clean]) == 0

    # corrupted run: plain verification replay FINDS divergence (exit 2)
    assert replay_main([flip]) == 2

    # the bisector pins the step and the exact flipped leaf, and emits
    # the kind="divergence" forensic record into --json
    forensics = str(tmp_path / "forensics.jsonl")
    assert replay_main([flip, "--bisect", "--json", forensics]) == 0
    records = [json.loads(l) for l in open(forensics)]
    (div,) = [r for r in records if r["kind"] == "divergence"]
    assert div["found"] is True
    # flip applied after step 3 -> the step-4 checkpoint carries it ->
    # first divergent step is 4 and the leaf set is EXACT
    assert div["step"] == 4
    assert div["exact_leaves"] is True
    assert div["leaves"] == ["[0]" + flip_event["path"]]
    assert div["clean_anchor"] == 2 and div["dirty_anchor"] == 4
    # replay booked its own machine time as goodput spans
    span_phases = {r["phase"] for r in records if r["kind"] == "span"}
    assert {"ckpt_restore", "step"} <= span_phases
