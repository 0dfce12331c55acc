"""The repo's documented allowlist: every intentional flagged construct.

This file is the single home of "yes, we mean it" for the static
auditors. EVERY entry carries its numerical/engineering reason — the
:class:`~apex_tpu.analysis.findings.AllowlistEntry` constructor rejects
bare entries — and lint-scope entries (``require_hit=True``) go stale
loudly when the construct they document disappears.

Organization: precision entries first (why each wide-dtype island in a
bf16 step is intentional), then collective-safety, then the compiled-HLO
comms entries, then the sharding/autofix entries, then the source-lint
entries, then the concurrency entries (every hand-proof the static
race/deadlock analyzer's findings rest on — the lock-free handshakes,
the deliberate blocking-under-lock sites, the audited teardown
handlers).
When the precision auditor flags a NEW site, the choice is binary: fix
the promotion, or add an entry HERE with the reason a reviewer can
check. See docs/analysis.md.
"""

from apex_tpu.analysis.findings import Allowlist, AllowlistEntry

__all__ = ["REPO_ALLOWLIST", "repo_allowlist"]

_PRECISION = [
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/ops/layer_norm.py",
        reason=(
            "norm statistics in f32: mean/variance of bf16 activations "
            "(~1e-3 squared terms) lose all significance in an 8-bit "
            "mantissa; the kernel reduces in f32 and casts back (the "
            "reference's AffineMixedDtypes contract)"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/transformer/layer.py",
        reason=(
            "norm affine params cast to f32 for the f32 norm kernels, "
            "and their grad transposes back into low-precision masters "
            "when params_dtype is bf16 — the activation upcast that used "
            "to live in _activate was a REAL finding and was fixed, not "
            "allowlisted"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/ops/attention.py",
        reason=(
            "softmax statistics in f32: bf16 exp/sum over long rows "
            "overflows and loses the max-subtraction guard; scores and "
            "probabilities are f32, the context matmul returns to bf16 "
            "(flash-attention's accumulator contract)"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/ops/softmax.py",
        reason=(
            "same softmax-statistics-in-f32 contract as ops/attention.py "
            "for the standalone fused softmax"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/parallel/layers.py",
        reason=(
            "master-weight casts: kernels/biases/embeddings are stored "
            "f32 (params_dtype) and cast to the compute dtype per use; "
            "the flagged bf16->f32 converts are the TRANSPOSES of those "
            "casts — gradients accumulating back into f32 masters, the "
            "whole point of O2 mixed precision"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/models/gpt.py",
        reason=(
            "embedding-output cast to compute dtype: its transpose "
            "accumulates embedding gradients in f32 — same master-weight "
            "contract as parallel/layers.py"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/models/bert.py",
        reason=(
            "BERT head/pooler params are f32 masters cast to compute "
            "dtype; flagged converts are the f32 gradient transposes"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/parallel/cross_entropy.py",
        reason=(
            "vocab-parallel CE computes logits stats (max, sum-exp, "
            "target logit) in f32: bf16 logsumexp over a 32k-vocab row "
            "is catastrophically lossy and the psum'ed partials must "
            "not saturate"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/parallel/ddp.py",
        reason=(
            "gradient allreduce in f32: summing N bf16 gradient replicas "
            "in bf16 loses low-order contributions exactly when N is "
            "large; the psum runs on f32 and casts back"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/parallel/ring_attention.py",
        reason=(
            "ring/blockwise attention carries f32 running max/sum/output "
            "accumulators across ring steps (the online-softmax "
            "recurrence is unstable in bf16)"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/parallel/sync_batch_norm.py",
        reason=(
            "cross-replica batch-norm statistics in f32 (variance via "
            "E[x^2]-E[x]^2 cancels catastrophically in bf16)"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/transformer/moe.py",
        reason=(
            "router math in f32: expert logits/softmax/aux-loss need "
            "exact tie-breaking and the load-balancing loss is a mean of "
            "tiny products; dispatched expert outputs re-enter bf16"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/transformer/utils.py",
        reason=(
            "grad-norm / param-norm sums of squares in f32 (sum of many "
            "small squares underflows bf16), and average_losses stacks "
            "scalars in f32"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/optimizers/",
        reason=(
            "master-weight f32 accumulations: fused/distributed "
            "optimizers keep moments and master params in f32 and "
            "unscale bf16/f16 grads into f32 before the update (O2 "
            "semantics; ref apex FusedAdam master path)"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/amp/",
        reason=(
            "the amp machinery's own unscale/master casts: grads are "
            "promoted to f32 exactly once at the optimizer boundary "
            "(grad_scaler.unscale, cast_engine master params)"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/ops/xentropy.py",
        reason=(
            "fused cross-entropy logsumexp statistics in f32 (same "
            "contract as parallel/cross_entropy.py)"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/resilience/sentinel.py",
        reason=(
            "anomaly-sentinel EMA/variance state is f32 by construction; "
            "a bf16 loss entering the z-score math is promoted once per "
            "step (a scalar)"
        ),
    ),
    AllowlistEntry(
        rule="precision.promotion",
        match="apex_tpu/monitor/metrics.py",
        reason=(
            "MetricBag folds scalars in f32 (interval means of bf16 "
            "losses would quantize visibly); one scalar per metric per "
            "step"
        ),
    ),
]

_COLLECTIVE = [
    AllowlistEntry(
        rule="collective.dead-traffic",
        match="apex_tpu/amp/grad_scaler.py",
        reason=(
            "found_inf psum over a possibly-size-1 model-parallel axis "
            "is replication-ESTABLISHING, not traffic: XLA elides the "
            "size-1 reduce (zero bytes) but checked shard_map "
            "(check_vma=True) relies on the psum to type the "
            "result replicated — gating it on axis size breaks "
            "out_specs inference on degenerate tp=1/pp=1 meshes "
            "(verified by repro)"
        ),
    ),
]

_COMMS = [
    # The HLO comms differ (analysis/hlo/comms_diff.py) cross-checks
    # XLA's emitted collectives against the xray ledger's trace-time
    # prediction. The known transpose-derived BACKWARD collectives — the
    # reversed mates of the TP gather/scatter mappings, sited by XLA at
    # the forward call sites in parallel/layers.py, models/gpt.py and
    # transformer/layer.py — are PREDICTED (the mappings' custom_vjp
    # pairs run their collectives through the ledger wrappers, PR-3) and
    # therefore match; they need no entries, and adding any would hide a
    # future regression that drops the custom_vjp pairing. What remains
    # is the one legitimate divergence XLA creates on its own:
    AllowlistEntry(
        rule="comms.folded",
        match="<step:*",
        reason=(
            "XLA legitimately emits FEWER reductions than traced: CSE "
            "folds byte-identical psums (the duplicated vocab-parallel "
            "CE stats over tp) and reassociation turns per-microbatch "
            "grad psums into one post-sum all-reduce — info-severity "
            "bookkeeping, suppressed here so the gate's record stream "
            "stays fully explained"
        ),
    ),
    AllowlistEntry(
        rule="comms.quantized",
        match="<step:*",
        reason=(
            "POSITIVE confirmation, not a defect: the differ verified "
            "8-bit-payload collectives (the parallel/compress.py "
            "quantized decomposition on the gpt-dp2tp2-int8 target) "
            "matched ledger predictions — recorded here so the gate's "
            "jsonl stays fully explained (every record allowlisted with "
            "a reason); the pattern's PRESENCE is separately pinned by "
            "tests/test_compress.py::TestLedgerPin, so suppressing it "
            "cannot hide a regression"
        ),
    ),
    AllowlistEntry(
        rule="comms.async",
        match="<step:*",
        reason=(
            "POSITIVE confirmation, not a defect: the differ verified "
            "that ledger-matched collectives were emitted as async "
            "-start/-done pairs (the overlap-aware schedules' proof "
            "loop: prefetched ZeRO param gathers, zero-bubble p2p "
            "edges) — recorded so the gate's jsonl stays fully "
            "explained. Backend-dependent by design: CPU XLA emits "
            "sync collectives, so the finding fires on TPU compiles "
            "only; the mechanism itself is pinned on synthetic async "
            "HLO by tests/test_analysis.py"
        ),
    ),
    # NO comms.vanished entry: nothing vanishes on the repo targets today
    # (CSE shortfalls are partial, so they land in comms.folded above),
    # and a whole predicted bucket disappearing — e.g. the dp grad
    # all-reduce going dead — is exactly the regression the differ
    # exists to catch. Allowlist matches on site, and vanished findings
    # all share the target's step site, so any entry here would mute
    # EVERY vanished bucket for the target, not one known case.
]

_SHARDING = [
    AllowlistEntry(
        rule="sharding.unverifiable",
        match="<hlo:*",
        reason=(
            "CPU jit compiles leave the entry ROOT without sharding "
            "annotations (GSPMD only stamps result shardings when a "
            "device assignment forces them), so output replication is "
            "honestly NOT audited on the CPU gate — recorded instead of "
            "silently skipped (degrade-loudly). The PARAM half of the "
            "audit still runs (entry parameters always carry shardings) "
            "and tests/test_autofix.py pins that the rule fires on the "
            "seeded naive target, so suppressing the info record cannot "
            "hide the auditor going blind"
        ),
    ),
    AllowlistEntry(
        rule="autofix.prescription",
        match="*",
        reason=(
            "a prescription is the FIX, not a defect: the defect it "
            "fixes (sharding.replicated-param, donation.missed, "
            "comms.reshard) is already on the stream under its own "
            "rule, and --fix exits nonzero itself when prescriptions "
            "remain unapplied or the apply is not idempotent — the "
            "info record exists so the jsonl carries the machine-"
            "applicable fix= payload"
        ),
    ),
]

_HBM = [
    AllowlistEntry(
        rule="memory.reconciled",
        match="<step:*",
        reason=(
            "POSITIVE confirmation, not a defect: the hlo-memory differ "
            "reconciled every resident component of the analytic ledger "
            "exactly against memory_analysis() (params + optimizer state "
            "digit-for-digit on the gpt targets) with temps inside the "
            "declared band — recorded so the gate's jsonl stays fully "
            "explained; the exact byte pins live in "
            "tests/test_memory_diff.py, so suppressing the info record "
            "cannot hide a regression"
        ),
    ),
    AllowlistEntry(
        rule="memory.overpredicted",
        match="<step:*",
        reason=(
            "model pessimism is information, not a defect: XLA aliasing "
            "or rematerializing bytes the ledger booked means the "
            "feasibility oracle over-refuses by the reported delta — "
            "worth reading, never worth failing the gate"
        ),
    ),
    AllowlistEntry(
        rule="memory.unverifiable",
        match="<step:*",
        reason=(
            "the bert, pipeline and autofix (gpt-zero-naive) targets "
            "carry no analytic ledger yet (StepTarget.hbm is None — "
            "their closed forms are ROADMAP follow-ups); the differ "
            "says so honestly instead of skipping. The gpt targets DO reconcile, and the examples' "
            "--xray-hbm treats unverifiable as NOT ok, so this cannot "
            "mask a platform that stops reporting memory_analysis()"
        ),
    ),
    # NO memory.unpredicted or memory.headroom entries: an argument
    # component the ledger cannot account for is a model bug to fix,
    # and a headroom breach is a capacity decision — neither is ever
    # explained away here.
]

_LINT = [
    AllowlistEntry(
        rule="lint.raw-collective",
        match="apex_tpu/monitor/xray/ledger.py",
        reason=(
            "the ledger's wrappers ARE the instrumented call sites — the "
            "one place raw lax collectives are allowed to live"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.compressed-collective",
        match="apex_tpu/parallel/compress.py",
        reason=(
            "the audited home: compress.py IS the one place quantize/"
            "dequant may compose with ledgered collectives — it records "
            "the true wire payloads (int8 + fp32 scales) in the ledger, "
            "owns the error-feedback residual semantics, and carries the "
            "poisoned-scale found_inf contract the unit tests pin"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.prefetch-gather",
        match="apex_tpu/optimizers/distributed_fused_adam.py",
        reason=(
            "the blessed home: zero_prefetch_gather IS the bucketed "
            "param-gather pipeline — its loop issues one ledgered "
            "all_gather per bucket by design, with overlap depth from "
            "choose_overlap_buckets (the ICI roofline) and an exact "
            "reconstruction transpose; both ZeRO optimizers route "
            "through it so the three invariants live once"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.hlo-text",
        match="apex_tpu/analysis/hlo/parser.py",
        reason=(
            "the parser is the single HLO-scraping home: module_text() "
            "is the one blessed .as_text() call; every other consumer "
            "hands the Lowered/Compiled object to the shared, "
            "nesting-safe parse functions"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.memory-api",
        match="apex_tpu/monitor/xray/hbm/",
        reason=(
            "the hbm package IS the blessed memory-API home: live.py's "
            "device_watermarks() is the one .memory_stats() call site "
            "and report.py's report_from_compiled() the one "
            ".memory_analysis() call site — every other consumer routes "
            "through them so None-vs-fake-zero has one convention"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.trace-file",
        match="apex_tpu/monitor/xray/timeline/",
        reason=(
            "the timeline package IS the blessed trace-event reader: the "
            "parser's suffix constants, glob messages, and format "
            "docstrings are the one place the trace-event filename "
            "marker may live (the lint.hlo-text/parser.py contract, "
            "applied to XProf's export)"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.trace-file",
        match="apex_tpu/analysis/lint.py",
        reason=(
            "the rule's own home: its docstring, detection literal, and "
            "finding message necessarily spell the format marker they "
            "police"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.trace-file",
        match="apex_tpu/monitor/xray/__init__.py",
        reason=(
            "the xray package index DOCUMENTS the format by name while "
            "routing readers to the timeline parser — documentation of "
            "where to go, not an ad-hoc reader"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.span-phases",
        match="apex_tpu/monitor/goodput/spans.py",
        reason=(
            "the span ledger's own implementation: span()/begin_span() "
            "forward their (runtime-validated) phase argument into "
            "Span, and Span.close forwards self.phase into emit_span — "
            "the one module where a non-literal phase is the mechanism, "
            "not a taxonomy leak; Span.__init__ raises on any string "
            "outside PHASES"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.signal-handlers",
        match="apex_tpu/utils/autoresume.py",
        reason=(
            "blessed home #1: AutoResume's preemption handler (flag + "
            "grace-budget arrival timestamp only, no IO) and the "
            "close()-time restoration of the previous disposition — the "
            "registration every other preemption consumer must route "
            "through"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.signal-handlers",
        match="apex_tpu/monitor/router.py",
        reason=(
            "blessed home #2: the router teardown's best-effort SIGTERM "
            "span-flush hook, which installs only over SIG_DFL so "
            "AutoResume's handler keeps precedence and re-raises so the "
            "process still dies by SIGTERM"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.process-exit",
        match="apex_tpu/resilience/health/responder.py",
        reason=(
            "the ONE deliberate hard-exit home: the incident "
            "responder's coordinated self-termination must use "
            "os._exit because a wedged main thread can run neither "
            "signal handlers nor atexit hooks — the responder performs "
            "the teardown (span flush, pending-save tombstone) itself "
            "from the watchdog thread and then ends the process with "
            "ExitCode.INCIDENT; sys.exit would raise into a thread "
            "that cannot unwind"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.silent-except",
        match="apex_tpu/monitor/router.py",
        reason=(
            "the PR-7 teardown blanket guards (_flush_all_routers): the "
            "atexit/SIGTERM flush runs when the process is already dying "
            "and the sinks ARE the reporting channel — a raising flush "
            "hook or sink close would mask the real exit path, and there "
            "is nowhere left to log a failure durably"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.silent-except",
        match="apex_tpu/monitor/watchdog.py",
        reason=(
            "ProfilerTrigger.close's abort-capture guard: stop_trace on "
            "an already-torn capture raises backend-dependently at end "
            "of run, and the PR-6 contract is losing-a-trace-must-not-"
            "lose-the-run — the abort happens during shutdown where a "
            "warning would be noise about a capture nobody will read"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.jit-donate",
        match="apex_tpu/training/gpt_step.py",
        reason=(
            "audited entrypoint: the GPT example's train_step is "
            "BUILT here (the one shared home the replayer rebuilds "
            "bit-identical steps from); its donation is verified by the "
            "donation auditor (--audit-donation and the example test)"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.jit-donate",
        match="examples/llama/finetune_llama.py",
        reason=(
            "audited entrypoint: the llama train step's params+opt-state "
            "donation is verified by the donation auditor "
            "(--audit-donation and the example test)"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.jit-donate",
        match="apex_tpu/serving/engine.py",
        reason=(
            "audited entrypoint: the serving engine's AOT-compiled "
            "prefill/decode steps donate the block-allocated KV pool "
            "(the whole point of the donated pytree: steady-state "
            "serving reuses one HBM allocation in place); realized "
            "donation is pinned empirically by the serving selftest "
            "gate — the pre-tick pool buffer must be deleted"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.jit-donate",
        match="apex_tpu/analysis/donation.py",
        reason=(
            "the donation auditor itself constructs the donating jit in "
            "order to introspect XLA's realized aliasing"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.jit-donate",
        match="apex_tpu/analysis/passes.py",
        reason=(
            "lower_step is the auditors' shared AOT lowering recipe: it "
            "constructs the donating jit whose realized aliasing the "
            "donation auditor and the compiled-HLO passes introspect"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.trace-emit",
        match="apex_tpu/serving/trace/emit.py",
        reason=(
            "the ONE blessed kind=\"trace\" construction site: "
            "TraceEmitter._emit is where every span record is built, so "
            "span ids, parent links, attempt tags and the start/dur_s "
            "schema stay consistent across engine, fleet and handoff "
            "emitters — the lint.raw-collective/ledger.py contract, "
            "applied to the request x-ray"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.trace-emit",
        match="apex_tpu/serving/trace/slo.py",
        reason=(
            "the ONE blessed kind=\"slo\" construction site: "
            "SLOMonitor.poll emits the burn-rate record after draining "
            "its tap, so window/violations/burn_rate/alert fields are "
            "computed in one place with the documented rolling-window "
            "semantics"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.nondeterminism",
        match="apex_tpu/resilience/retry.py",
        reason=(
            "the retry jitter home: (rng or random).random() de-"
            "stampedes a FLEET of hosts retrying the same flaky "
            "filesystem — host-side sleep scheduling only, never step "
            "math; callers needing determinism inject rng= (the tests "
            "do) or pass jitter=0 (the single-writer save path does)"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.nondeterminism",
        match="apex_tpu/monitor/router.py",
        reason=(
            "the record-timestamp home: make_record's time.time() is "
            "the shared schema's 't' field — metadata every record "
            "carries for human/log correlation, joined on 'step' (never "
            "on 't') and never an input to any computation; the replay "
            "comparisons ignore it by construction"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.thread-create",
        match="apex_tpu/monitor/watchdog.py",
        reason=(
            "a blessed thread home: the watchdog monitor loop and the "
            "escalation ladder OWN thread lifecycle — named daemon "
            "threads, stop-event + join(timeout) on close, and the "
            "ProfilerTrigger _state_lock handshake for cross-thread "
            "capture requests; both Thread sites here are the "
            "inventoried concurrency roots the analyzer audits"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.thread-create",
        match="apex_tpu/resilience/health/responder.py",
        reason=(
            "a blessed thread home: the hard-exit escalation timer — a "
            "daemon Thread that os._exit()s if the cooperative drain "
            "wedges, i.e. the one thread that must NOT share lifecycle "
            "discipline with anything it might be escalating past; its "
            "root is inventoried and its reach audited by the "
            "handler-safety pass"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="lint.thread-create",
        match="apex_tpu/utils/checkpoint.py",
        reason=(
            "a blessed thread home: finalize_async's single background "
            "finalizer thread, whose handle the autoresume save "
            "handshake tracks (wait() joins it before the manifest "
            "commit) — the identity-swap protocol the concurrency "
            "allowlist entry on autoresume.py documents"
        ),
        require_hit=True,
    ),
]

# ----------------------------------------------------------------------
# concurrency: the static race/deadlock analyzer's documented hand-proofs
# (apex_tpu/analysis/concurrency). Every entry quotes the invariant the
# flagged construct rests on; require_hit=True because the analyzer sees
# the whole package every run — change the code and the entry goes stale,
# forcing the proof to be re-made.
# ----------------------------------------------------------------------

_CONCURRENCY = [
    AllowlistEntry(
        rule="concurrency.unguarded-write",
        match="apex_tpu/utils/autoresume.py",
        reason=(
            "the documented lock-free handshakes (autoresume module "
            "docstring): (1) the _pending identity-swap — save() "
            "installs a fresh dict, the background finalizer commits "
            "only `if self._pending is pending` and clears only `if "
            "self._pending is pending`, so a newer save wins by "
            "identity, never by field mutation; (2) the GIL-atomic flag "
            "stores _signaled/_signal_t/_requested/_sigterm_t/"
            "_abandoned_step — single machine-word rebinds written by "
            "the signal handler or the finalizer thread and only READ "
            "(never read-modify-written) elsewhere. Both are "
            "deliberately lock-free: the writer is a signal handler "
            "(may not take locks — see concurrency.handler-unsafe) or "
            "a finalizer that must never block the step loop"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.blocking-under-lock",
        match="apex_tpu/_native.py",
        reason=(
            "the compile-once guard: _load() holds _LOCK across the "
            "g++ subprocess + atomic rename ON PURPOSE — the lock's "
            "whole job is making every other thread wait for the ONE "
            "build instead of racing N compilers at the same .so; the "
            "per-pid temp + os.replace keeps an interrupted build from "
            "poisoning the mtime cache, and _LOCK nests nothing (leaf "
            "lock, no cycle possible)"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.blocking-under-lock",
        match="apex_tpu/monitor/router.py",
        reason=(
            "the sink fan-out IS the lock's purpose: MetricRouter._lock "
            "exists to serialize emit() against close() so a record "
            "never lands on a half-torn sink list; sink.emit under it "
            "is the invariant, not a bug. The lock is reentrant "
            "(RLock) and LEAF in the repo's order — no sink calls back "
            "into the router — so it can stall, never deadlock"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.blocking-under-lock",
        match="apex_tpu/resilience/remediation/",
        reason=(
            "the controller's one-way lock order: controller._lock -> "
            "router._lock (via _emit's router.event) and never the "
            "reverse — the router knows nothing about the controller, "
            "so the order cannot invert and the pair cannot cycle. The "
            "state.py makedirs/rename under the same lock is the "
            "persist-atomicity contract: the decision and its durable "
            "record must be one critical section, or a crash between "
            "them replays a restart budget it already spent"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.unbounded-wait",
        match="apex_tpu/resilience/chaos.py",
        reason=(
            "wedge() blocking forever is the FEATURE: the chaos drill's "
            "hung-collective stand-in must be indistinguishable from a "
            "real wedge (no timeout, nothing for except to catch) so "
            "the escalating watchdog — not the wedge — ends the job; "
            "timeout_s bounds it for unit tests only"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.unbounded-wait",
        match="apex_tpu/ops/attention.py",
        reason=(
            "not a host wait: the paged decode kernel's cp.wait() is a "
            "Pallas DMA descriptor's wait on its semaphore, traced into "
            "the Mosaic kernel body (_paged_decode_kernel) — every wait "
            "follows the start of the same copy in program order, and a "
            "DMA semaphore has no timeout to give"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.unbounded-wait",
        match="apex_tpu/utils/autoresume.py",
        reason=(
            "the durability barrier: _commit's self._writer.wait() "
            "joins the single background finalizer before the manifest "
            "commit — unbounded BY CONTRACT because a checkpoint is "
            "either durable or the save did not happen; bounding it "
            "would invent a third state (manifest written, payload "
            "maybe not). The watchdog's deadline, not a local timeout, "
            "is the escape hatch for a wedged filesystem"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.handler-unsafe",
        match="apex_tpu/monitor/router.py",
        reason=(
            "the audited teardown: _flush_all_routers runs registered "
            "flush hooks (dynamic fn()) and router.close() from "
            "atexit/SIGTERM — each call is wrapped in except-and-drop "
            "(teardown must never raise), the router lock it takes is "
            "REENTRANT, and every flush path tolerates partial state; "
            "the hooks are registered only by the goodput span "
            "accountant, whose flush is lock-free"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.handler-unsafe",
        match="apex_tpu/utils/autoresume.py",
        reason=(
            "the coordinated handler chain: TerminationNotice's "
            "handler is flag-only (GIL-atomic stores, no locks) and "
            "then chains prev(signum, frame) — dynamic, but the chain "
            "is coordinated by construction: it skips the router "
            "teardown hook (checked by marker attribute, because that "
            "hook re-raises to DIE by the signal the notice exists to "
            "survive) and every other registrant in the repo is "
            "flag-style (lint.signal-handlers closes the set of homes)"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.unresolved",
        match="apex_tpu/",
        reason=(
            "the resolver's honest remainder: calls through variables, "
            "stored callbacks and injected fns that pure-AST resolution "
            "cannot follow from a thread root — surfaced as info so "
            "reviewers see exactly where the analyzer's reach ends, "
            "suppressed as a class because each is a visibility note, "
            "not a defect claim"
        ),
        require_hit=True,
    ),
    AllowlistEntry(
        rule="concurrency.shared-state",
        match="apex_tpu/",
        reason=(
            "the benign sharing inventory: single-writer-many-reader "
            "handshakes (GIL-atomic stores, legal by the same proof as "
            "the autoresume entry) and reads-only state — named "
            "patterns surfaced as info so the sharing stays deliberate "
            "and reviewable, suppressed as a class because neither "
            "pattern can lose an update"
        ),
        require_hit=True,
    ),
]

REPO_ALLOWLIST = Allowlist(
    _PRECISION + _COLLECTIVE + _COMMS + _SHARDING + _HBM + _LINT
    + _CONCURRENCY
)


def repo_allowlist() -> Allowlist:
    """A fresh copy of the repo allowlist (callers may extend)."""
    return Allowlist(list(REPO_ALLOWLIST.entries))
