"""Static-analysis subsystem (apex_tpu.analysis): jaxpr auditors, AST
lint framework, compiled-HLO passes, allowlist machinery, and the repo
self-check.

Every pass gets a hand-built miniature step with ONE known violation
(bad promotion, rejected donation, non-permutation ppermute, mismatched
pipeline edge, host callback, mis-sharded matmul, transpose-synthesized
backward collective, dead psum, oversized replicated entry buffer)
asserting exact Finding fields, plus a clean-function negative test —
the auditors must find exactly what is seeded and nothing else. The HLO
side additionally pins the GPT dp2xtp2 target's hand-counted collective
inventory (per-axis op counts AND bytes, exact). The self-check at the
bottom is the acceptance gate: ``python -m apex_tpu.analysis`` (lint +
jaxpr + HLO passes over the GPT/BERT step targets on the dp2xtp2 CPU
mesh) must exit 0 against the repo as committed.
"""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.compat import shard_map
from apex_tpu.monitor.xray import ledger as xlax
from jax.sharding import PartitionSpec as P

from apex_tpu.analysis import (
    Allowlist,
    AllowlistEntry,
    Finding,
    StepTarget,
    merge_findings,
    run_passes,
)
from apex_tpu.analysis.donation import audit_donation
from apex_tpu.analysis.lint import run_lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

THIS_FILE = "tests/test_analysis.py"


def mesh1d(n, name):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), (name,))


def mesh2d(a, b, names):
    return jax.sharding.Mesh(
        np.array(jax.devices()[: a * b]).reshape(a, b), names
    )


# ---------------------------------------------------------------------------
# findings + allowlist machinery


class TestFindingsAndAllowlist:
    def test_bare_allowlist_entry_rejected(self):
        with pytest.raises(ValueError, match="reason"):
            AllowlistEntry(rule="precision.promotion", match="x.py", reason="  ")

    def test_entry_matching_rule_glob_and_site(self):
        e = AllowlistEntry(
            rule="precision.*", match="apex_tpu/ops/", reason="stats in f32"
        )
        hit = Finding(rule="precision.promotion", message="m",
                      site="apex_tpu/ops/layer_norm.py:52")
        miss_rule = Finding(rule="donation.missed", message="m",
                            site="apex_tpu/ops/layer_norm.py:52")
        miss_site = Finding(rule="precision.promotion", message="m",
                            site="apex_tpu/models/gpt.py:1")
        assert e.matches(hit)
        assert not e.matches(miss_rule)
        assert not e.matches(miss_site)

    def test_merge_findings_sums_counts(self):
        a = Finding(rule="r", message="m", site="s", count=2)
        b = Finding(rule="r", message="m", site="s", count=3)
        c = Finding(rule="r", message="m", site="other")
        merged = merge_findings([a, b, c])
        assert sorted(f.count for f in merged) == [1, 5]

    def test_apply_partitions_and_detects_stale(self):
        al = Allowlist([
            AllowlistEntry(rule="r", match="ok.py", reason="fine"),
            AllowlistEntry(rule="r", match="gone.py", reason="was fine",
                           require_hit=True),
        ])
        res = al.apply([Finding(rule="r", message="m", site="ok.py:1"),
                        Finding(rule="r", message="m", site="bad.py:1")])
        assert [f.site for f in res.findings] == ["bad.py:1"]
        assert len(res.suppressed) == 1
        assert [e.match for e in res.stale_entries] == ["gone.py"]
        assert not res.ok

    def test_info_findings_do_not_fail(self):
        res = Allowlist().apply(
            [Finding(rule="r", message="m", site="s", severity="info")]
        )
        assert res.ok

    def test_records_share_router_schema(self):
        from apex_tpu import monitor

        res = Allowlist([
            AllowlistEntry(rule="r", match="b.py", reason="documented why"),
        ]).apply([
            Finding(rule="r", message="kept", site="a.py:1"),
            Finding(rule="r", message="hidden", site="b.py:2"),
        ])
        mem = monitor.MemorySink()
        router = monitor.MetricRouter([mem])
        for rec in res.to_records(step=7):
            router.emit(rec)
        assert len(mem.records) == 2
        for rec in mem.records:
            assert {"t", "step", "kind", "rule", "site"} <= set(rec)
            assert rec["kind"] == "analysis" and rec["step"] == 7
        allowed = [r for r in mem.records if r["allowed"]]
        assert len(allowed) == 1 and allowed[0]["reason"] == "documented why"

    def test_repo_allowlist_every_entry_carries_a_reason(self):
        from apex_tpu.analysis.allowlist import REPO_ALLOWLIST

        assert len(REPO_ALLOWLIST) > 0
        for e in REPO_ALLOWLIST.entries:
            # a reason must be a sentence someone can review, not a token
            assert len(e.reason.split()) >= 5, (e.rule, e.match)


# ---------------------------------------------------------------------------
# precision auditor


class TestPrecisionPass:
    def test_seeded_promotion_exact_fields(self):
        def step(x):
            return x.astype(jnp.float32).sum()  # the seeded violation

        tgt = StepTarget(
            name="seeded", fn=step,
            args=(jax.ShapeDtypeStruct((4,), jnp.bfloat16),),
        )
        (f,) = run_passes(tgt, passes=["precision"])
        assert f.rule == "precision.promotion"
        assert f.severity == "error"
        assert f.target == "seeded"
        assert f.count == 1
        assert f.data == {"from": "bfloat16", "to": "float32"}
        assert f.site.startswith(THIS_FILE + ":")

    def test_promotion_found_inside_nested_scan(self):
        def step(x):
            def body(c, _):
                return c + x.astype(jnp.float32).sum(), None

            out, _ = jax.lax.scan(body, 0.0, None, length=3)
            return out

        tgt = StepTarget(
            name="t", fn=step, args=(jax.ShapeDtypeStruct((4,), jnp.bfloat16),)
        )
        fins = run_passes(tgt, passes=["precision"])
        assert [f.rule for f in fins] == ["precision.promotion"]

    def test_f64_flagged(self):
        def step(x):
            return x.astype(jnp.float64) * 2

        with jax.enable_x64(True):
            tgt = StepTarget(
                name="t", fn=step,
                args=(jax.ShapeDtypeStruct((2,), jnp.float32),),
            )
            fins = run_passes(tgt, passes=["precision"])
        rules = {f.rule for f in fins}
        assert rules == {"precision.f64"}
        assert all(f.severity == "error" for f in fins)
        prims = {f.data["primitive"] for f in fins}
        assert "convert_element_type" in prims

    def test_clean_bf16_step_no_findings(self):
        # no reduction on purpose: jnp.sum of a bf16 array upcasts its
        # accumulator to f32 (a REAL promotion the pass would flag)
        def step(x, w):
            return jnp.tanh(x @ w) * 2

        tgt = StepTarget(
            name="t", fn=step,
            args=(jax.ShapeDtypeStruct((4, 4), jnp.bfloat16),
                  jax.ShapeDtypeStruct((4, 4), jnp.bfloat16)),
        )
        assert run_passes(tgt, passes=["precision"]) == []


# ---------------------------------------------------------------------------
# collective-safety validator


class TestCollectivePass:
    def test_unknown_axis_flagged(self):
        mesh_dp = mesh1d(2, "dp")
        mesh_tp = mesh1d(2, "tp")  # the ambient mesh the pass audits against

        @functools.partial(
            shard_map, mesh=mesh_dp, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def step(x):
            return xlax.psum(x, "dp")

        tgt = StepTarget(name="t", fn=step, args=(jnp.ones((2,)),),
                         mesh=mesh_tp)
        fins = run_passes(tgt, passes=["collective"])
        (f,) = [f for f in fins if f.rule == "collective.unknown-axis"]
        assert f.severity == "error"
        assert f.data == {"op": "psum", "axis": "dp"}
        assert f.site.startswith(THIS_FILE + ":")

    def test_size1_axis_flagged_as_dead_traffic(self):
        mesh = mesh2d(2, 1, ("dp", "pp"))

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def step(x):
            return xlax.psum(x, "pp")  # size-1 axis: dead traffic

        # the ledger elides size-1 axes from RECORDING, but the primitive
        # is still in the jaxpr — exactly what this pass exists to flag
        tgt = StepTarget(name="t", fn=step, args=(jnp.ones((2,)),), mesh=mesh)
        (f,) = run_passes(tgt, passes=["collective"])
        assert f.rule == "collective.dead-traffic"
        assert f.severity == "warning"
        assert f.data == {"op": "psum", "axis": "pp"}

    def test_non_permutation_ppermute_flagged(self):
        mesh = mesh1d(4, "pp")

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def step(x):
            # rank 0 sends twice: not a permutation (jax traces it fine,
            # which is why the static check exists)
            return xlax.ppermute(x, "pp", [(0, 1), (0, 2)])

        (f,) = run_passes(StepTarget(name="t", fn=step, args=(jnp.ones((2,)),),
                                     mesh=mesh), passes=["collective"])
        assert f.rule == "collective.non-permutation"
        assert f.severity == "error"
        assert "duplicate source" in f.message
        assert f.data["axis"] == "pp"

    def test_mismatched_pipeline_edge_flagged(self):
        mesh = mesh1d(4, "pp")

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def step(x):
            # stage 1's outgoing edge is missing: stages 2..3 wait on a
            # stream that never crosses the gap
            return xlax.ppermute(x, "pp", [(0, 1), (2, 3)])

        (f,) = run_passes(StepTarget(name="t", fn=step, args=(jnp.ones((2,)),),
                                     mesh=mesh), passes=["collective"])
        assert f.rule == "collective.mismatched-edge"
        assert f.severity == "error"
        assert f.data["gaps"] == "[1]"

    def test_p2p_edge_grammar_is_clean(self):
        """Every edge constructor in parallel/pipeline/p2p.py must pass
        the validator — the schedules build all their edges from these."""
        from apex_tpu.parallel.pipeline import p2p

        mesh = mesh1d(4, "pp")
        for edges in (p2p.forward_edges(4), p2p.backward_edges(4),
                      p2p.ring_edges(4), p2p.last_to_first_edges(4)):

            @functools.partial(
                shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False,
            )
            def step(x, edges=edges):
                return xlax.ppermute(x, "pp", edges)

            fins = run_passes(StepTarget(name="t", fn=step,
                                         args=(jnp.ones((2,)),), mesh=mesh),
                              passes=["collective"])
            assert fins == [], (edges, [f.format() for f in fins])

    def test_real_pipeline_schedule_validates_clean(self):
        """The 1F1B schedule (fwd AND the transposed backward edges jax
        synthesizes through the scan) contains only valid chains."""
        from apex_tpu.parallel.pipeline import schedules

        mesh = mesh1d(4, "pp")

        def stage_fn(p, x):
            return jnp.tanh(x @ p)

        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False,
        )
        def step(p, mb, tg):
            loss, _, grads = (
                schedules.forward_backward_pipelining_without_interleaving(
                    stage_fn, loss_fn, p, mb, tg, axis_name="pp"
                )
            )
            return loss

        p = jnp.ones((4, 4))
        mb = jnp.ones((4, 2, 4))
        fins = run_passes(StepTarget(name="pp1f1b", fn=step, args=(p, mb, mb),
                                     mesh=mesh), passes=["collective"])
        assert fins == [], [f.format() for f in fins]

    def test_chain_gaps_unit(self):
        from apex_tpu.analysis.collectives import chain_gaps

        assert chain_gaps([(0, 1), (1, 2), (2, 3)], 4) == []
        assert chain_gaps([(1, 0), (2, 1), (3, 2)], 4) == []
        assert chain_gaps([(0, 1), (2, 3)], 4) == [1]
        assert chain_gaps([(0, 1), (3, 4)], 8) == [1, 2]
        # rings / wrap edges / shuffles have no linear-chain semantics
        assert chain_gaps([(0, 1), (1, 2), (2, 3), (3, 0)], 4) is None
        assert chain_gaps([(3, 0)], 4) is None
        assert chain_gaps([(0, 2), (2, 0)], 4) is None


# ---------------------------------------------------------------------------
# host-sync detector


class TestHostSyncPass:
    def test_debug_print_flagged(self):
        def step(x):
            jax.debug.print("loss={l}", l=x.sum())  # the seeded violation
            return x * 2

        (f,) = run_passes(
            StepTarget(name="t", fn=step, args=(jnp.ones((4,)),)),
            passes=["host-sync"],
        )
        assert f.rule == "host-sync.callback"
        assert f.severity == "error"
        assert f.data == {"primitive": "debug_print"}
        assert f.site.startswith(THIS_FILE + ":")

    def test_pure_callback_flagged(self):
        def step(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2,
                jax.ShapeDtypeStruct((4,), jnp.float32), x,
            )
            return y.sum()

        (f,) = run_passes(
            StepTarget(name="t", fn=step, args=(jnp.ones((4,)),)),
            passes=["host-sync"],
        )
        assert f.rule == "host-sync.callback"
        assert f.data == {"primitive": "pure_callback"}

    def test_clean_step_no_findings(self):
        def step(x):
            return (x @ x).sum()

        assert run_passes(
            StepTarget(name="t", fn=step, args=(jnp.ones((4, 4)),)),
            passes=["host-sync"],
        ) == []


# ---------------------------------------------------------------------------
# donation auditor


class TestDonationAuditor:
    MiB = 1 << 20

    def test_rejected_donation_exact_fields(self):
        def step(a, b):
            return b * 2.0  # 'a' donated but no output matches it

        a = jax.ShapeDtypeStruct((512, 512), jnp.float32)  # 1 MiB
        b = jax.ShapeDtypeStruct((8,), jnp.float32)
        fins = audit_donation(step, a, b, donate_argnums=(0,),
                              arg_names=("a", "b"), target="seeded")
        (f,) = [f for f in fins if f.rule == "donation.rejected"]
        assert f.severity == "error"
        assert f.data["leaf"] == "a"
        assert f.data["stage"] == "lowering"
        assert f.data["bytes"] == self.MiB
        assert f.target == "seeded"

    def test_missed_donation_flagged(self):
        def step(p, o, x):
            new_p = jax.tree_util.tree_map(lambda l: l - 0.1 * x.sum(), p)
            new_o = jax.tree_util.tree_map(lambda l: l + 1.0, o)
            return new_p, new_o

        p = {"w": jax.ShapeDtypeStruct((512, 512), jnp.float32)}
        o = {"m": jax.ShapeDtypeStruct((512, 512), jnp.float32)}
        x = jax.ShapeDtypeStruct((4,), jnp.float32)
        # p donated, o forgotten: o matches an un-aliased output
        fins = audit_donation(step, p, o, x, donate_argnums=(0,),
                              arg_names=("params", "opt_state", "x"))
        (f,) = [f for f in fins if f.rule == "donation.missed"]
        assert f.severity == "warning"
        assert f.data["leaf"] == "opt_state['m']"
        assert f.data["bytes"] == self.MiB

    def test_clean_donation_no_findings(self):
        def step(p, o, x):
            new_p = jax.tree_util.tree_map(lambda l: l - 0.1 * x.sum(), p)
            new_o = jax.tree_util.tree_map(lambda l: l + 1.0, o)
            return new_p, new_o

        p = {"w": jax.ShapeDtypeStruct((512, 512), jnp.float32)}
        o = {"m": jax.ShapeDtypeStruct((512, 512), jnp.float32)}
        x = jax.ShapeDtypeStruct((4,), jnp.float32)
        assert audit_donation(step, p, o, x, donate_argnums=(0, 1)) == []

    def test_prejitted_step_uses_its_own_donation(self):
        def step(p, x):
            return jax.tree_util.tree_map(lambda l: l - x.sum(), p)

        p = {"w": jnp.ones((512, 512))}
        x = jnp.ones((4,))
        jitted = jax.jit(step, donate_argnums=(0,))
        assert audit_donation(jitted, p, x) == []

    def test_pass_skipped_without_donation_intent(self):
        tgt = StepTarget(name="t", fn=lambda x: x * 2,
                         args=(jnp.ones((4,)),), donate_argnums=None)
        assert run_passes(tgt, passes=["donation"]) == []


# ---------------------------------------------------------------------------
# AST lint framework


class TestLintFramework:
    def test_raw_collective_seeded(self):
        files = {
            "apex_tpu/fake.py":
                "from jax import lax\n\n\ndef f(x):\n"
                "    return lax.psum(x, 'tp')\n",
        }
        (f,) = run_lint(rules=["lint.raw-collective"], files=files)
        assert f.rule == "lint.raw-collective"
        assert f.site == "apex_tpu/fake.py:5"
        assert f.data == {"op": "psum"}

    def test_raw_collective_docstring_mention_not_flagged(self):
        files = {
            "apex_tpu/fake.py":
                '"""docs mention jax.lax.psum freely"""\n'
                "# and comments: lax.all_gather\n",
        }
        assert run_lint(rules=["lint.raw-collective"], files=files) == []

    def test_float64_seeded(self):
        files = {
            "apex_tpu/fake.py":
                "import jax.numpy as jnp\nimport numpy as np\nimport numpy\n"
                "x = jnp.float64(3.0)\n"
                "y = np.float64(3.0)  # host-side: fine\n"
                "z = numpy.float64(3.0)  # host-side too: fine\n"
                "w = jax.numpy.float64(3.0)\n",
        }
        fins = run_lint(rules=["lint.float64"], files=files)
        # only the jax spellings: jnp.float64 and jax.numpy.float64
        assert sorted(f.site for f in fins) == [
            "apex_tpu/fake.py:4", "apex_tpu/fake.py:7",
        ]
        assert all(f.rule == "lint.float64" for f in fins)

    def test_rule_scopes_enforced_by_registry(self):
        # raw-collective is scoped to apex_tpu/: the same violation under
        # examples/ is out of scope and must not be flagged
        files = {
            "examples/fake.py":
                "from jax import lax\n\n\ndef f(x):\n"
                "    return lax.psum(x, 'tp')\n",
        }
        assert run_lint(rules=["lint.raw-collective"], files=files) == []

    def test_jit_donate_seeded_and_data_calls_exempt(self):
        files = {
            "examples/fake.py":
                "import functools, jax\n"
                "step = jax.jit(lambda x: x, donate_argnums=(0,))\n"
                "tgt = StepTarget(fn=step, donate_argnums=(0,))\n"
                "part = functools.partial(jax.jit, donate_argnums=(1,))\n",
        }
        fins = run_lint(rules=["lint.jit-donate"], files=files)
        # the jax.jit call and the partial(jax.jit) are flagged; the
        # StepTarget DECLARATION (auditing intent, not a jit) is not
        assert sorted(f.site for f in fins) == [
            "examples/fake.py:2", "examples/fake.py:4",
        ]

    def test_signal_handlers_seeded(self):
        # raw registration in library code (both the plain and the repo's
        # `import signal as _signal` spellings) and the import-hiding
        # `from signal import signal` form are all flagged
        files = {
            "apex_tpu/fake.py":
                "import signal\nimport signal as _signal\n"
                "signal.signal(signal.SIGTERM, lambda *a: None)\n"
                "_signal.signal(_signal.SIGINT, lambda *a: None)\n"
                "from signal import signal\n",
            "examples/fake.py":
                "import signal\n"
                "signal.signal(signal.SIGTERM, lambda *a: None)\n",
        }
        fins = run_lint(rules=["lint.signal-handlers"], files=files)
        assert sorted(f.site for f in fins) == [
            "apex_tpu/fake.py:3", "apex_tpu/fake.py:4",
            "apex_tpu/fake.py:5", "examples/fake.py:2",
        ]
        assert all(f.rule == "lint.signal-handlers" for f in fins)

    def test_signal_handlers_reads_not_flagged(self):
        # getsignal / SIG constants / os.kill are reads or delivery, not
        # registration — the rule polices rewiring only
        files = {
            "apex_tpu/fake.py":
                "import os, signal as _signal\n"
                "h = _signal.getsignal(_signal.SIGTERM)\n"
                "os.kill(os.getpid(), _signal.SIGTERM)\n",
        }
        assert run_lint(rules=["lint.signal-handlers"], files=files) == []

    def test_signal_handlers_blessed_homes_allowlisted(self):
        # the two homes exist, are flagged by the raw rule, and are the
        # ONLY apex_tpu/examples sites (require_hit entries go stale if
        # either registration moves)
        fins = run_lint(rules=["lint.signal-handlers"])
        homes = {f.site.rsplit(":", 1)[0] for f in fins}
        assert homes == {"apex_tpu/utils/autoresume.py",
                         "apex_tpu/monitor/router.py"}

    def test_nondeterminism_seeded(self):
        files = {
            "apex_tpu/fake.py":
                "import random, time\n"
                "import numpy as np\n"
                "a = random.random()\n"
                "b = np.random.rand(3)\n"
                "c = time.time()\n"
                "d = (None or random).uniform(0, 1)\n",
        }
        fins = run_lint(rules=["lint.nondeterminism"], files=files)
        assert sorted(f.site for f in fins) == [
            "apex_tpu/fake.py:3", "apex_tpu/fake.py:4",
            "apex_tpu/fake.py:5", "apex_tpu/fake.py:6",
        ]
        assert {f.data["call"] for f in fins} == {
            "random.random", "np.random.rand", "time.time",
            "random.uniform",
        }

    def test_nondeterminism_seeded_constructs_and_clocks_exempt(self):
        # seeded constructors PIN determinism, jax.random is functional,
        # and monotonic clocks are durations — none of these are the
        # unreproducible inputs the rule polices
        files = {
            "apex_tpu/fake.py":
                "import random, time\n"
                "import numpy as np\n"
                "import jax\n"
                "rng = np.random.RandomState(0)\n"
                "g = np.random.default_rng(7)\n"
                "r = random.Random(3)\n"
                "x = rng.uniform(0, 1)\n"
                "y = random.Random(3).random()\n"
                "z = r.random()\n"
                "random.seed(0)\n"
                "np.random.seed(0)\n"
                "k = jax.random.uniform(jax.random.PRNGKey(0), (2,))\n"
                "t0 = time.monotonic(); t1 = time.perf_counter()\n",
        }
        assert run_lint(rules=["lint.nondeterminism"], files=files) == []

    def test_nondeterminism_repo_scan_fully_explained(self):
        # the ONLY library sites are the two allowlisted homes (retry
        # jitter, record timestamps) — anything new must carry a reason
        fins = run_lint(rules=["lint.nondeterminism"])
        homes = {f.site.rsplit(":", 1)[0] for f in fins}
        assert homes == {"apex_tpu/resilience/retry.py",
                         "apex_tpu/monitor/router.py"}
        from apex_tpu.analysis.allowlist import repo_allowlist as _ral

        res = _ral().apply(fins, check_stale=False)
        assert res.ok

    def test_serving_clock_seeded(self):
        files = {
            "apex_tpu/serving/fake.py":
                "import time\n"
                "import time as _time\n"
                "from time import monotonic\n"
                "a = time.time()\n"
                "b = time.monotonic()\n"
                "c = _time.monotonic_ns()\n",
        }
        fins = run_lint(rules=["lint.serving-clock"], files=files)
        assert sorted(f.site for f in fins) == [
            "apex_tpu/serving/fake.py:3", "apex_tpu/serving/fake.py:4",
            "apex_tpu/serving/fake.py:5", "apex_tpu/serving/fake.py:6",
        ]
        assert {f.data.get("call") for f in fins if "call" in f.data} == {
            "time.time", "time.monotonic", "time.monotonic_ns",
        }

    def test_serving_clock_injection_idiom_exempt(self):
        # the injected-default REFERENCE is the idiom the rule protects;
        # perf_counter is a duration probe and sleep is not a read —
        # none of them feed deadline math off a hidden clock
        files = {
            "apex_tpu/serving/fake.py":
                "import time\n"
                "def f(time_fn=time.monotonic):\n"
                "    now = time_fn()\n"
                "    t0 = time.perf_counter()\n"
                "    time.sleep(0.0)\n"
                "    return now\n",
        }
        assert run_lint(rules=["lint.serving-clock"], files=files) == []
        # scoped to apex_tpu/serving/ only: elsewhere bare clock reads
        # are lint.nondeterminism's business, not this rule's
        outside = {
            "apex_tpu/utils/fake.py": "import time\nt = time.time()\n",
        }
        assert run_lint(rules=["lint.serving-clock"], files=outside) == []

    def test_serving_clock_repo_scan_clean(self):
        # the serving tree speaks injected-clock everywhere, with no
        # allowlist entries needed
        assert run_lint(rules=["lint.serving-clock"]) == []

    def test_registered_taps_seeded(self):
        files = {
            "apex_tpu/fake.py":
                "def mod(self, x):\n"
                "    self.sow('intermediates', 'not_a_real_tap', x)\n",
        }
        fins = run_lint(rules=["lint.registered-taps"], files=files)
        seeded = [f for f in fins if f.data.get("tap") == "not_a_real_tap"]
        assert len(seeded) == 1
        assert seeded[0].site == "apex_tpu/fake.py:2"
        assert not seeded[0].data.get("stale")

    def test_hlo_text_seeded(self):
        files = {
            "apex_tpu/fake.py":
                "def dump(compiled):\n"
                "    return compiled.as_text()\n",
        }
        (f,) = run_lint(rules=["lint.hlo-text"], files=files)
        assert f.rule == "lint.hlo-text"
        assert f.site == "apex_tpu/fake.py:2"
        assert f.severity == "error"

    def test_hlo_text_docstring_mention_not_flagged(self):
        files = {
            "apex_tpu/fake.py":
                '"""docs may say .as_text() freely"""\n'
                "# comments too: compiled.as_text()\n"
                "s = 'as_text'\n",
        }
        assert run_lint(rules=["lint.hlo-text"], files=files) == []

    def test_trace_file_seeded(self):
        # a glob/suffix string is a reader's fingerprint, wherever it
        # appears — docstrings included (unlike hlo-text's NAME tokens,
        # the format marker only ever appears as a string)
        files = {
            "apex_tpu/fake.py":
                "import gzip\n"
                "SUFFIX = '.trace.json.gz'\n",
            "examples/fake2.py":
                '"""reads the *.trace.json export by hand"""\n',
        }
        fins = run_lint(rules=["lint.trace-file"], files=files)
        assert sorted(f.site for f in fins) == [
            "apex_tpu/fake.py:2", "examples/fake2.py:1",
        ]
        assert all(f.rule == "lint.trace-file" for f in fins)
        assert all(f.severity == "error" for f in fins)

    def test_trace_file_fstring_flagged(self):
        # 3.12+ tokenizes f-strings as FSTRING_* (literal text in
        # FSTRING_MIDDLE), not STRING — the rule must catch the reader
        # fingerprint in both spellings on every supported python
        files = {
            "apex_tpu/fake.py": 'p = f"{host}.trace.json.gz"\n',
        }
        (f,) = run_lint(rules=["lint.trace-file"], files=files)
        assert f.site == "apex_tpu/fake.py:1"

    def test_trace_file_comment_mention_not_flagged(self):
        files = {
            "apex_tpu/fake.py":
                "# the parser owns .trace.json reading\n"
                "x = 1\n",
        }
        assert run_lint(rules=["lint.trace-file"], files=files) == []

    def test_unknown_rule_raises(self):
        with pytest.raises(KeyError, match="lint.nope"):
            run_lint(rules=["lint.nope"], files={})


# ---------------------------------------------------------------------------
# compiled-HLO parser (analysis/hlo/parser.py)


SYNTHETIC_HLO = """\
HloModule test_mod, input_output_alias={ {0}: (0, {}, may-alias), {1, 2}: (3, {}, must-alias) }, num_partitions=4

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

%while_body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element((s32[], f32[4]{0}) %p), index=1
  %ar.1 = f32[4]{0} all-reduce(f32[4]{0} %x), channel_id=1, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add.1, metadata={op_name="while/psum" source_file="/repo/a.py" source_line=10}
  %i = s32[] get-tuple-element((s32[], f32[4]{0}) %p), index=0
  ROOT %t = (s32[], f32[4]{0}) tuple(s32[] %i, f32[4]{0} %ar.1)
}

ENTRY %main.9 (p0: f32[4], p1: f32[8,8], p2: f32[2,4]) -> (f32[8], f32[4], f32[4]) {
  %p0 = f32[4]{0} parameter(0), sharding={replicated}, metadata={op_name="params[\\'w\\']"}
  %p1 = f32[8,8]{1,0} parameter(1), sharding={devices=[2,1,2]<=[4] last_tile_dim_replicate}, metadata={op_name="tokens"}
  %p2 = f32[2,4]{1,0} parameter(2), sharding={devices=[1,1,4]<=[4] last_tile_dim_replicate}
  %ags = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %p0), channel_id=2, replica_groups={{0,1},{2,3}}, dimensions={0}, metadata={op_name="jit(f)/all_gather" source_file="/repo/b.py" source_line=20}
  %agd = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ags)
  %cp = f32[4]{0} collective-permute(f32[4]{0} %p0), channel_id=3, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
  ROOT %r = (f32[8]{0}, f32[4]{0}, f32[4]{0}) tuple(f32[8]{0} %agd, f32[4]{0} %cp, f32[4]{0} %p0)
}
"""


class TestHloParser:
    def test_balanced_is_nesting_safe(self):
        from apex_tpu.analysis.hlo.parser import balanced

        body, end = balanced("x={a={b}, c={d={e}}} tail", 2)
        assert body == "a={b}, c={d={e}}"
        assert end == 19
        with pytest.raises(ValueError):
            balanced("{unclosed", 0)

    def test_balanced_skips_quoted_braces(self):
        # XLA carries a user named_scope verbatim into op_name, so a
        # quoted metadata string may contain braces: an unmatched one
        # must not crash the scan, a matched one must not truncate it
        from apex_tpu.analysis.hlo.parser import balanced

        body, _ = balanced('x={op_name="scope{x" k={v}} tail', 2)
        assert body == 'op_name="scope{x" k={v}'
        body, _ = balanced('x={op_name="a{b}c" k=1} tail', 2)
        assert body == 'op_name="a{b}c" k=1'

    def test_braced_named_scope_in_metadata_parses(self):
        from apex_tpu.analysis.hlo.parser import parse_hlo_module

        hlo = SYNTHETIC_HLO.replace(
            'op_name="while/psum"', 'op_name="while/odd{scope/psum"'
        )
        mod = parse_hlo_module(hlo)
        ar = next(c for c in mod.collectives if c.kind == "all-reduce")
        assert ar.op_name == "while/odd{scope/psum"
        assert ar.source_file == "/repo/a.py" and ar.source_line == 10

    def test_realized_aliases_nested_output_indices(self):
        from apex_tpu.analysis.hlo.parser import realized_aliases

        # tuple output index {1, 2} must map through nesting-safely
        assert realized_aliases(SYNTHETIC_HLO) == {0: 0, 3: 1}

    def test_parse_synthetic_module(self):
        from apex_tpu.analysis.hlo.parser import parse_hlo_module

        mod = parse_hlo_module(SYNTHETIC_HLO)
        assert mod.name == "test_mod"
        assert mod.entry_name == "main.9"
        # collectives everywhere: the while-body all-reduce is found, the
        # -start async form normalizes to its sync kind, -done is skipped
        kinds = sorted(c.kind for c in mod.collectives)
        assert kinds == ["all-gather", "all-reduce", "collective-permute"]
        ar = next(c for c in mod.collectives if c.kind == "all-reduce")
        assert ar.computation == "while_body.2"
        # iota shorthand [2,2]<=[4] expands row-major
        assert ar.replica_groups == ((0, 1), (2, 3))
        assert ar.channel_id == 1
        assert ar.source_file == "/repo/a.py" and ar.source_line == 10
        assert ar.operands[0].elements == 4 and ar.operands[0].nbytes == 16
        ag = next(c for c in mod.collectives if c.kind == "all-gather")
        assert ag.computation == "main.9"
        # ledger convention: the operand (local shard), not the result
        assert ag.elements == 4
        assert ag.op_name == "jit(f)/all_gather"
        # permutes print source_target_pairs, not replica_groups
        cp = next(c for c in mod.collectives
                  if c.kind == "collective-permute")
        assert cp.replica_groups == ()
        assert cp.source_target_pairs == ((0, 1), (1, 0), (2, 3), (3, 2))
        # entry params with shardings and jax's human labels
        assert [p.index for p in mod.entry_params] == [0, 1, 2]
        p0, p1, p2 = mod.entry_params
        assert p0.sharding.fully_replicated and p0.label == "params[\\'w\\']"
        assert not p1.sharding.fully_replicated  # tiled over a real axis
        assert p2.sharding.fully_replicated  # all tile dims 1 + replicate
        assert p1.shape.nbytes == 256
        assert [s.elements for s in mod.entry_root_shapes] == [8, 4, 4]

    def test_current_xla_text_bare_operands_and_stack_frames(self):
        """The installed XLA prints operands as bare ``%name`` references
        and source locations once, as index tables in the module header
        (metadata carries only ``stack_frame_id``). Operand bytes come from
        the named instruction's result shape; the site from the tables.
        (At the parent both read as zero bytes and an empty file.)"""
        from apex_tpu.analysis.hlo.parser import parse_hlo_module

        hlo = """HloModule jit_f, num_partitions=4

FileNames
1 "/repo/model.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=42 end_line=42 column=4 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}


%region_0.0 (a.0: f32[], b.1: f32[]) -> f32[] {
  %a.0 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a.0, %b.1)
}

ENTRY %main.0_spmd (param.1: f32[4,16]) -> f32[4,16] {
  %param.1 = f32[4,16]{1,0} parameter(0), sharding={replicated}
  ROOT %psum.7 = f32[4,16]{1,0} all-reduce(%param.1), channel_id=1, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%region_0.0, metadata={op_name="jit(f)/psum" stack_frame_id=1}
}
"""
        (ar,) = parse_hlo_module(hlo).collectives
        assert ar.kind == "all-reduce"
        assert ar.operands[0].elements == 64 and ar.nbytes == 256
        assert (ar.source_file, ar.source_line) == ("/repo/model.py", 42)

    def test_module_text_requires_as_text_or_str(self):
        from apex_tpu.analysis.hlo.parser import module_text

        assert module_text("HloModule x") == "HloModule x"
        with pytest.raises(TypeError, match="as_text"):
            module_text(42)


# ---------------------------------------------------------------------------
# replica_groups -> mesh-axis attribution


class TestHloAttribution:
    def test_partitions_and_classify_dp2tp2(self):
        from apex_tpu.analysis.hlo import attribution

        mesh = mesh2d(2, 2, ("dp", "tp"))
        parts = attribution.mesh_axis_partitions(mesh)
        labels = set(parts.values())
        assert labels == {"dp", "tp", "dp,tp"}
        classify = attribution.classify_replica_groups
        assert classify(mesh, ((0, 1), (2, 3))) == "tp"
        assert classify(mesh, ((0, 2), (1, 3))) == "dp"
        assert classify(mesh, ((0, 1, 2, 3),)) == "dp,tp"
        # implicit "everyone" and singleton groups
        assert classify(mesh, ()) == "dp,tp"
        assert classify(mesh, ((0,), (1,), (2,), (3,))) == attribution.AXIS_NONE
        # a partition no axis subset induces
        assert classify(mesh, ((0, 3), (1, 2))) == attribution.AXIS_UNKNOWN

    def test_classify_source_target_pairs(self):
        from apex_tpu.analysis.hlo import attribution

        mesh = mesh2d(2, 2, ("dp", "pp"))
        classify = attribution.classify_source_target_pairs
        # pp ring edges inside each dp group: the SMALLEST subset wins
        assert classify(mesh, ((0, 1), (1, 0), (2, 3), (3, 2))) == "pp"
        assert classify(mesh, ((0, 2), (2, 0), (1, 3), (3, 1))) == "dp"
        # an edge crossing both axes only fits the full-mesh subset
        assert classify(mesh, ((0, 3),)) == "dp,pp"
        assert classify(mesh, ()) == attribution.AXIS_NONE
        assert classify(mesh, ((0, 9),)) == attribution.AXIS_UNKNOWN

    def test_size1_axes_dropped(self):
        from apex_tpu.analysis.hlo import attribution

        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:4]).reshape(2, 1, 1, 2),
            ("dp", "pp", "cp", "tp"),
        )
        parts = attribution.mesh_axis_partitions(mesh)
        assert set(parts.values()) == {"dp", "tp", "dp,tp"}
        # ledger composite keys canonicalize: size-1 names drop, order is
        # mesh order, unknown names stay visible
        canon = attribution.canon_axis_key
        assert canon(mesh, "pp,cp,tp") == "tp"
        assert canon(mesh, "tp,dp") == "dp,tp"
        assert canon(mesh, "pp") == attribution.AXIS_NONE
        assert canon(mesh, "nope") == "nope"


# ---------------------------------------------------------------------------
# ghost-collective differ (analysis/hlo/comms_diff.py)


class TestHloComms:
    def mesh(self):
        return mesh2d(2, 2, ("dp", "tp"))

    def test_misharded_matmul_unpredicted(self):
        # the ISSUE's seeded positive: a matmul whose operands are
        # sharded along the contracting dim forces GSPMD to insert an
        # all-reduce no ledger wrapper ever saw
        from apex_tpu.analysis.hlo import audit_comms
        from jax.sharding import NamedSharding

        mesh = self.mesh()
        xs = jax.ShapeDtypeStruct((8, 64), jnp.float32,
                                  sharding=NamedSharding(mesh, P(None, "tp")))
        ws = jax.ShapeDtypeStruct((64, 8), jnp.float32,
                                  sharding=NamedSharding(mesh, P("tp", None)))
        f = jax.jit(lambda x, w: x @ w,
                    out_shardings=NamedSharding(mesh, P()))
        fins = audit_comms(f, xs, ws, mesh=mesh, target="seeded")
        (f1,) = [f for f in fins if f.rule == "comms.unpredicted"]
        assert f1.severity == "error"
        assert f1.data["op"] == "all-reduce"
        assert f1.data["axis"] == "tp"
        assert f1.data["elements"] == 64  # the (8,8) partial product
        assert f1.data["transpose"] is False
        assert f1.site.startswith(THIS_FILE + ":")  # the matmul's line

    def test_transpose_bwd_unpredicted_and_allowlisted(self):
        # a NON-custom_vjp all_gather under grad: jax's transpose rule
        # synthesizes the reduce-scatter mate, which never runs through
        # the ledger wrappers — the documented blind spot, now loud. The
        # reason-carrying allowlist is the sanctioned way to keep known
        # transpose-derived backward collectives.
        from apex_tpu.analysis.hlo import audit_comms

        mesh = self.mesh()

        # x sharded over BOTH axes: no dp broadcast in the forward, so
        # the only transpose-synthesized collective is the tp
        # reduce-scatter mate of the gather
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp", "tp"), out_specs=P(),
            check_vma=False,
        )
        def gathered_sum(x):
            return jnp.sum(xlax.all_gather(x, "tp"))

        def step(x):
            return jax.value_and_grad(gathered_sum)(x)

        x = jax.ShapeDtypeStruct((2, 8), jnp.float32)
        fins = audit_comms(step, x, mesh=mesh, target="seeded")
        rs = [f for f in fins if f.rule == "comms.unpredicted"
              and f.data["op"] == "reduce-scatter"]
        (f1,) = rs
        assert f1.severity == "error"
        assert f1.data["axis"] == "tp"
        assert f1.data["transpose"] is True
        assert "transpose-synthesized" in f1.message
        # the transposed op inherits the FORWARD call's source info —
        # the ledger wrapper line (the eqn_site quirk, passes.py)
        assert "ledger.py" in f1.site
        allow = Allowlist([AllowlistEntry(
            rule="comms.unpredicted",
            match="ledger.py",
            reason=(
                "transpose-derived backward mate of the forward "
                "all_gather: legitimate mirrored traffic the ledger "
                "cannot see without a custom_vjp pairing"
            ),
        )])
        res = allow.apply(fins, check_stale=False)
        assert not any(
            f.rule == "comms.unpredicted" for f in res.findings
        )
        assert any(
            f.rule == "comms.unpredicted" for f, _ in res.suppressed
        )

    def test_ledgered_ppermute_matches(self):
        # a predicted permute must MATCH its emitted collective-permute —
        # which XLA prints with source_target_pairs, not replica_groups
        # (the attribution goes through the pair graph)
        from apex_tpu.analysis.hlo import audit_comms

        mesh = mesh2d(2, 2, ("dp", "pp"))

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def step(x):
            return xlax.ppermute(x, "pp", [(0, 1), (1, 0)])

        fins = audit_comms(step, jax.ShapeDtypeStruct((16,), jnp.float32),
                           mesh=mesh, target="seeded")
        assert fins == [], [f.format() for f in fins]

    def test_dead_psum_vanished(self):
        from apex_tpu.analysis.hlo import audit_comms

        mesh = self.mesh()

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def step(x):
            xlax.psum(x, "tp")  # result unused: XLA deletes the traffic
            return x * 2.0

        fins = audit_comms(step, jax.ShapeDtypeStruct((16,), jnp.float32),
                           mesh=mesh, target="seeded")
        (f1,) = [f for f in fins if f.rule == "comms.vanished"]
        assert f1.severity == "warning"
        assert f1.data == {"op": "all-reduce", "axis": "tp", "elements": 16}

    def test_async_start_done_confirmed(self):
        """The overlap proof loop's emitted-HLO leg: a ledger-matched
        collective spelled as an async -start/-done pair yields the
        comms.async positive confirmation with predicted==emitted bytes
        (synthetic text: CPU XLA emits sync collectives, so the
        mechanism is pinned here and fires for real on TPU compiles)."""
        from apex_tpu.analysis.hlo import audit_comms
        from apex_tpu.analysis.hlo.parser import parse_hlo_module

        mesh = mesh1d(4, "dp")

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False,
        )
        def step(x):
            return xlax.all_gather(x, "dp", tiled=True)

        synthetic = """\
HloModule m

ENTRY %main.1 (p0: f32[8]) -> f32[32] {
  %p0 = f32[8]{0} parameter(0)
  %ags = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} %p0), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(step)/all_gather" source_file="/repo/apex_tpu/monitor/xray/ledger.py" source_line=419}
  ROOT %agd = f32[32]{0} all-gather-done((f32[8]{0}, f32[32]{0}) %ags)
}
"""
        # the parser records the async spelling (and skips the -done)
        mod = parse_hlo_module(synthetic)
        (c,) = mod.collectives
        assert c.kind == "all-gather" and c.is_async

        x = jax.ShapeDtypeStruct((32,), jnp.float32)
        fins = audit_comms(step, x, mesh=mesh, target="t",
                           compiled=synthetic)
        (f1,) = fins
        assert f1.rule == "comms.async"
        assert f1.severity == "info"
        assert f1.data == {"axis": "dp", "op": "all-gather", "ops": 1,
                           "bytes": 32}
        assert "predicted == emitted" in f1.message
        # sync spelling: same match, NO async confirmation
        sync = synthetic.replace(
            "(f32[8]{0}, f32[32]{0}) all-gather-start", "f32[32]{0} all-gather"
        ).replace(
            "ROOT %agd = f32[32]{0} all-gather-done((f32[8]{0}, "
            "f32[32]{0}) %ags)",
            "ROOT %agd = f32[32]{0} add(f32[32]{0} %ags, f32[32]{0} %ags)",
        )
        assert audit_comms(step, x, mesh=mesh, target="t",
                           compiled=sync) == []

    def test_unverifiable_without_mesh(self):
        from apex_tpu.analysis.hlo import audit_comms

        fins = audit_comms(lambda x: x * 2, jnp.ones((4,)), mesh=None,
                           target="t")
        (f1,) = fins
        assert f1.rule == "comms.unverifiable"
        assert f1.severity == "info"

    def test_unparseable_hlo_unverifiable_not_crash(self):
        # malformed module text (truncated alias header) must degrade to
        # the documented comms.unverifiable outcome, not a ValueError
        # that kills the whole gate
        from apex_tpu.analysis.hlo import audit_comms

        fins = audit_comms(
            lambda x: x * 2, jnp.ones((4,)), mesh=self.mesh(), target="t",
            compiled="HloModule m, input_output_alias={ {0",
        )
        (f1,) = fins
        assert f1.rule == "comms.unverifiable"
        assert f1.severity == "info"
        assert "could not be parsed" in f1.message

    def test_batched_reconcile_requires_leading_dim_split(self):
        # stage-2 guard: an emitted op whose size is coincidentally k*e
        # of a predicted bucket but whose operand dims do NOT factor as
        # (batch..., payload...) is a real unpredicted collective, not
        # vmap batching — it must survive to comms.unpredicted instead
        # of silently consuming k predictions
        from apex_tpu.analysis.hlo import audit_comms

        mesh = self.mesh()

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def step(x):
            with xlax.scaled(4):  # 4 predicted tp psums of 16 el
                return xlax.psum(x, "tp")

        x = jax.ShapeDtypeStruct((16,), jnp.float32)
        synthetic = """\
HloModule m

ENTRY %main.1 (p0: f32[{dims}]) -> f32[{dims}] {{
  %p0 = f32[{dims}]{{0}} parameter(0)
  ROOT %ar = f32[{dims}]{{0}} all-reduce(f32[{dims}]{{0}} %p0), channel_id=1, replica_groups={{{{0,1}},{{2,3}}}}, to_apply=%add, metadata={{op_name="jit(step)/mystery" source_file="/repo/c.py" source_line=5}}
}}
"""
        # 48 = 3*16 divides the bucket payload, but f32[48] is not a
        # 3-stack of f32[16] payloads in any leading-dim split
        fins = audit_comms(step, x, mesh=mesh, target="seeded",
                           compiled=synthetic.format(dims="48"))
        (f1,) = [f for f in fins if f.rule == "comms.unpredicted"]
        assert f1.data["op"] == "all-reduce"
        assert f1.data["axis"] == "tp"
        assert f1.data["elements"] == 48
        # the 4 predictions are then genuinely unconsumed -> vanished
        assert [f.rule for f in fins if f is not f1] == ["comms.vanished"]
        # positive control: a true vmap batch IS a leading-dim stack and
        # consumes the whole bucket cleanly
        fins = audit_comms(step, x, mesh=mesh, target="seeded",
                           compiled=synthetic.format(dims="4,16"))
        assert fins == [], [f.format() for f in fins]

    def test_gpt_dp2tp2_inventory_and_clean(self):
        """ACCEPTANCE: the hand-counted collective inventory of the GPT
        dp2xtp2 target's OPTIMIZED HLO, pinned exactly per (op, axis) in
        both counts and operand bytes (f32 on the CPU backend — XLA
        legalizes bf16 collectives to f32 there, which is exactly why
        the differ matches on elements, not bytes).

        The hand count (model: 2 layers, hidden 16, ffn 32, heads 2,
        vocab 32, seq 8, batch 2 over dp2 => per-shard b=1; SP over tp2
        => s/tp=4):

        - all-gather/tp, 10 ops x 64 el (4,1,16): SP activation gathers
          -- fwd qkv + h_to_4h per layer (4) + final pre-logits gather
          (1), and their custom_vjp backward mates at dense + 4h_to_h
          per layer (4) + the tied-embedding attend path (1).
        - reduce-scatter/tp, 9 ops x 128 el (8,1,16): fwd dense +
          4h_to_h per layer (4), bwd qkv + h_to_4h per layer (4), and
          the tied-embedding logits-grad path (1).
        - all-reduce/tp, 19 ops, 1508 B: 14 x 16-el grad psums for the
          tp-replicated LN scales/biases (5 norms x 2 params) and the
          SP dense/4h biases (4); 3 x 8-el vocab-parallel CE stats over
          the (1,8) token rows (pmax + sumexp psum + target-logit psum,
          the 4th predicted psum CSE-folds with the sumexp one); 1 x
          scalar found_inf psum (grad scaler); 1 x 128-el vocab-parallel
          embedding-grad psum.
        - all-reduce/dp, 29 ops, 15172 B: one grad psum per parameter
          leaf (28 leaves: 12 per layer + word/pos embeddings + final
          LN scale/bias) + the scalar loss pmean.
        - all-reduce/none, 1 op: the found_inf psum over the size-1
          pp/cp axes — singleton groups, zero bytes, elided by the
          ledger and skipped by the differ.

        And the differ itself must come back CLEAN on this target: only
        the info-severity comms.folded record for the CSE'd CE-stats
        psum (no unpredicted, no reshard, no vanished).
        """
        from apex_tpu.analysis import StepContext
        from apex_tpu.analysis.hlo import attribution, audit_comms
        from apex_tpu.analysis.hlo.parser import parse_hlo_module
        from apex_tpu.analysis.targets import dp2tp2_mesh, gpt_step_target

        mesh = dp2tp2_mesh()
        tgt = gpt_step_target(mesh)
        ctx = StepContext(tgt)
        _, compiled = ctx.aot()
        mod = parse_hlo_module(compiled)
        parts = attribution.mesh_axis_partitions(mesh)

        inventory = {}
        for c in mod.collectives:
            axis = attribution.classify_replica_groups(
                mesh, c.replica_groups, parts
            )
            count, nbytes = inventory.get((c.kind, axis), (0, 0))
            inventory[(c.kind, axis)] = (count + 1, nbytes + c.nbytes)

        hand_count = {
            ("all-gather", "tp"): (10, 10 * 64 * 4),
            ("reduce-scatter", "tp"): (9, 9 * 128 * 4),
            ("all-reduce", "tp"): (19, 14 * 16 * 4 + 3 * 8 * 4
                                   + 1 * 4 + 128 * 4),
            ("all-reduce", "dp"): (29, 15172),
            ("all-reduce", "none"): (1, 4),
        }
        # bytes are pinned exactly. all-reduce OP counts are an upper
        # bound: XLA's all-reduce combiner may merge the hand-counted
        # psums into fewer tuple-shaped all-reduces (the installed XLA
        # emits 5 over tp and 1 over dp) without changing a byte
        assert inventory.keys() == hand_count.keys()
        for key, (count, nbytes) in hand_count.items():
            got_count, got_bytes = inventory[key]
            assert got_bytes == nbytes, (key, got_bytes, nbytes)
            if key[0] == "all-reduce":
                assert 1 <= got_count <= count, (key, got_count, count)
            else:
                assert got_count == count, (key, got_count, count)
        # dp bytes cross-check: 28 f32 grad leaves = the full parameter
        # tree (3792 el) + the scalar loss pmean
        assert 15172 == 3792 * 4 + 4

        fins = audit_comms(
            tgt.fn, *tgt.args, mesh=mesh,
            donate_argnums=tgt.donate_argnums, target=tgt.name,
            compiled=compiled,
        )
        assert all(f.severity == "info" for f in fins), [
            f.format() for f in fins
        ]
        (folded,) = [f for f in fins if f.rule == "comms.folded"]
        assert folded.data == {
            "op": "all-reduce", "axis": "tp", "elements": 8,
        }

    def test_bert_clean(self):
        """Clean negative for the second CLI target: no error/warning
        comms findings, and the sharding auditor is silent (every entry
        buffer is tiny)."""
        from apex_tpu.analysis import StepContext
        from apex_tpu.analysis.hlo import audit_comms, audit_entry_shardings
        from apex_tpu.analysis.targets import bert_step_target, dp2tp2_mesh

        mesh = dp2tp2_mesh()
        tgt = bert_step_target(mesh)
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
        assert audit_entry_shardings(compiled, mesh, target=tgt.name) == []


# ---------------------------------------------------------------------------
# entry-sharding auditor (analysis/hlo/sharding_audit.py)


class TestHloSharding:
    def test_replicated_param_flagged_sharded_clean(self):
        from apex_tpu.analysis.hlo import audit_entry_shardings
        from jax.sharding import NamedSharding

        mesh = mesh2d(2, 2, ("dp", "tp"))
        big = jax.ShapeDtypeStruct((512, 1024), jnp.float32,
                                   sharding=NamedSharding(mesh, P()))
        small = jax.ShapeDtypeStruct((8,), jnp.float32,
                                     sharding=NamedSharding(mesh, P()))
        compiled = jax.jit(lambda a, b: (a * 2.0, b + 1.0)).lower(
            big, small
        ).compile()
        fins = audit_entry_shardings(compiled, mesh, target="seeded")
        # the small buffer is exempt by the 1 MiB floor
        (f1,) = [f for f in fins if f.severity == "warning"]
        assert f1.rule == "sharding.replicated-param"
        assert f1.data["bytes"] == 512 * 1024 * 4
        assert f1.data["index"] == 0
        # CPU jit leaves the ROOT unannotated and the 2 MiB result is
        # above the floor: the auditor must SAY outputs went unaudited
        # (degrade-loudly) instead of silently skipping them
        (u,) = [f for f in fins if f.rule == "sharding.unverifiable"]
        assert u.severity == "info"
        assert u.data["outputs"] >= 1

        sharded = jax.ShapeDtypeStruct(
            (512, 1024), jnp.float32,
            sharding=NamedSharding(mesh, P("dp", None)),
        )
        compiled2 = jax.jit(lambda a: a * 2.0).lower(sharded).compile()
        fins2 = audit_entry_shardings(compiled2, mesh, target="s")
        assert [f.rule for f in fins2 if f.severity != "info"] == []
        assert {f.rule for f in fins2} <= {"sharding.unverifiable"}

    def test_silent_without_parallel_axes(self):
        from apex_tpu.analysis.hlo import audit_entry_shardings

        mesh = mesh1d(1, "dp")
        assert audit_entry_shardings("HloModule x", mesh) == []
        assert audit_entry_shardings("HloModule x", None) == []


# ---------------------------------------------------------------------------
# the repo self-check: the CLI gate must pass against the tree as committed


class TestRepoSelfCheck:
    def test_hlo_passes_registered(self):
        # the CLI gate runs every registered pass: the HLO family must
        # be in the registry or the gate silently loses its coverage
        from apex_tpu.analysis import JAXPR_PASSES

        assert {"precision", "donation", "collective", "host-sync",
                "hlo-comms", "hlo-sharding"} <= set(JAXPR_PASSES)

    def test_repo_lint_clean(self):
        """All source rules over the real tree, repo allowlist applied:
        zero unallowlisted findings and zero stale entries."""
        from apex_tpu.analysis import Allowlist
        from apex_tpu.analysis.allowlist import REPO_ALLOWLIST

        fins = run_lint()
        lint_entries = [
            e for e in REPO_ALLOWLIST.entries if e.rule.startswith("lint.")
        ]
        res = Allowlist(lint_entries).apply(fins, check_stale=True)
        assert not res.findings, "\n".join(f.format() for f in res.findings)
        assert not res.stale_entries, res.stale_entries

    def test_cli_main_clean(self):
        """ACCEPTANCE: the full gate — AST rules + all four jaxpr passes
        over the GPT dp2xtp2 and BERT step builders — exits 0. Any future
        silent promotion, broken donation, raw collective, or in-step
        host callback fails this test."""
        from apex_tpu.analysis.__main__ import main

        try:
            assert main([]) == 0
        finally:
            # the CLI points parallel_state at a 4-device sub-mesh;
            # restore the full default mesh for whatever test runs next
            from apex_tpu.parallel import parallel_state

            parallel_state.initialize_model_parallel()

    def test_gpt_pp_target_zero_comms_suppressions(self):
        """CI satellite (ISSUE 14): the zero-bubble pp target audits
        with ZERO comms-allowlist suppressions — no unpredicted /
        reshard / vanished findings exist at all, because the schedule
        hand-writes its backward edges through the ledgered p2p wrappers
        and the ZeRO prefetch gathers are ledger-routed. Only the
        broadly-allowlisted positive/bookkeeping rules (comms.folded,
        comms.async, comms.quantized) may appear."""
        from apex_tpu.analysis import targets as targets_mod
        from apex_tpu.analysis.allowlist import repo_allowlist

        try:
            target = targets_mod.gpt_pp_step_target()
            fins = run_passes(target)
        finally:
            from apex_tpu.parallel import parallel_state

            parallel_state.initialize_model_parallel()
        bad = [f for f in fins if f.rule in (
            "comms.unpredicted", "comms.reshard", "comms.vanished",
            "comms.unverifiable",
        )]
        assert bad == [], "\n".join(f.format() for f in bad)
        res = repo_allowlist().apply(fins, check_stale=False)
        assert res.ok, "\n".join(f.format() for f in res.findings)


def test_analysis_cli_subprocess(tmp_path):
    """The real entry point, as CI would run it: ``python -m
    apex_tpu.analysis`` in a fresh process (its own env setup), exit 0,
    and every emitted record an allowlisted finding with a reason."""
    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    out = str(tmp_path / "analysis.jsonl")
    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.analysis", "--json", out],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=570,
    )
    assert proc.returncode == 0, (
        f"analysis CLI failed\nstdout tail: {proc.stdout[-2000:]}\n"
        f"stderr tail: {proc.stderr[-800:]}"
    )
    records = [json.loads(l) for l in open(out)]
    assert records, "CLI emitted no analysis records"
    for rec in records:
        assert rec["kind"] == "analysis"
        assert rec["allowed"] is True
        assert rec["reason"].strip()
