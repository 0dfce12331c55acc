"""GPT pretraining: indexed dataset + samplers + TP/SP mesh + checkpoints.

The end-to-end composition the reference spreads across
examples + testing/standalone_gpt.py + Megatron launchers: a GPT LM
trained from a memory-mapped token corpus through the native data path
(apex_tpu.data), Megatron-style tensor/sequence parallelism over a mesh,
FusedAdam, dynamic loss scaling, named timers, and orbax checkpoints.

Telemetry (apex_tpu.monitor, docs/observability.md): the step folds loss,
grad norm, loss scale, sentinel z-score and skip counts into an on-device
``MetricBag`` and the host fetches it ONCE per ``--log-interval``; records
(incl. tokens/s and analytic MFU) fan out to stdout and, with
``--metrics-jsonl``/``--metrics-csv``/``--tensorboard-dir``, to file
sinks — the anomaly stream below shares the same record schema. A stall
watchdog (``--step-deadline``) arms the incident ladder over wedged
steps (warn -> forensic ``kind="incident"`` dump -> opt-in coordinated
self-termination, ``apex_tpu.resilience.health``) and
``--profile-step`` / sentinel escalation snapshot a profiler trace
window under ``--profile-dir``.

Resilience (apex_tpu.resilience, docs/resilience.md): the step carries an
anomaly-sentinel state next to the scaler state; loss spikes / NaNs gate
the update inside the compiled step, and the host escalates skip ->
rollback (in-memory snapshot ring + data-iterator rewind + LR dampen) ->
halt-and-checkpoint. Checkpoints are manifest-verified; restore falls
back past torn or bit-flipped step dirs. ``--chaos-*`` flags inject all
three fault classes so the whole recovery ladder is drivable from the
command line:

Replay & forensics (apex_tpu.resilience.replay, docs/resilience.md
"Replay & forensics"): with ``--save`` the run journals by default — the
training step itself is built by the ONE shared builder
(``apex_tpu.training.build_gpt_training``, recorded in the
journal header), every step's batch ids/crc + chaos arms + lr_scale +
loss/verdict/layer_rms fingerprints land as ``kind="journal"`` records
plus the ``<save>/replay-journal.jsonl`` sidecar, and every checkpoint
is a replay anchor. A flagged run is then mechanically reproducible:
``python -m apex_tpu.resilience.replay <save-dir> --bisect`` re-executes
from the nearest verified checkpoint and pins a divergence to the step
and leaf (drivable here with ``--chaos-bitflip-step``, the silent
in-memory corruption the sentinel misses).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
    python examples/gpt/pretrain_gpt.py --steps 12 --hidden 64 --layers 2 \\
        --seq-len 64 --micro-batch 2 --global-batch 16 --save /tmp/ck \\
        --save-interval 4 --chaos-nan-steps 5 --chaos-sigterm-step 9

CPU smoke (8 virtual devices, synthetic corpus):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
    python examples/gpt/pretrain_gpt.py --steps 5 --tp 2 --hidden 64 \\
        --layers 2 --seq-len 64 --micro-batch 2 --global-batch 8
"""

import argparse
import contextlib
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="TPU GPT pretraining")
    p.add_argument("--corpus", default=None,
                   help="token file prefix (see apex_tpu.data.write_token_file);"
                        " default: a synthetic corpus in a temp dir")
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--arch-file", default=None,
                   help="a published config.json naming the model "
                        "(apex_tpu/models/arch.py; docs/models.md): its "
                        "widths replace --layers/--hidden/--heads/--vocab")
    p.add_argument("--layers-kept", type=int, default=None,
                   help="with --arch-file: the first N layers (this "
                        "program's pipeline share); default all")
    p.add_argument("--experts-held", type=int, default=None,
                   help="with --arch-file: routed experts of each layer "
                        "held here (this program's expert-parallel share); "
                        "default all")
    p.add_argument("--first-expert", type=int, default=0,
                   help="with --experts-held: the first expert held")
    p.add_argument("--vocab-rows", type=int, default=None,
                   help="with --arch-file: rows of the embedding and the "
                        "head held here; default the published vocabulary")
    p.add_argument("--mtp-loss-coeff", type=float, default=0.3,
                   help="weight of the multi-token-prediction loss")
    p.add_argument("--router-bias-update-speed", type=float, default=0.0,
                   help="with --arch-file: what each expert's router bias "
                        "moves by after a step, towards an even load "
                        "(balancing without an auxiliary loss; DeepSeek-V3 "
                        "trained with 0.001); 0 holds the bias fixed")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sequence-parallel", action=argparse.BooleanOptionalAction,
                   default=True, help="Megatron SP over tp (--no-sequence-parallel to disable)")
    p.add_argument("--micro-batch", type=int, default=4)
    p.add_argument("--global-batch", type=int, default=16)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--save", default=None, help="checkpoint directory")
    p.add_argument("--save-interval", type=int, default=100)
    p.add_argument("--keep-last-n", type=int, default=None,
                   help="checkpoint retention: keep only the newest N steps")
    p.add_argument("--background-finalize",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="verify + commit async interval saves on the "
                        "writer's background thread (ckpt_save badput "
                        "collapses to issuance-only); "
                        "--no-background-finalize restores the blocking "
                        "commit-at-next-save behavior — deterministic for "
                        "preemption drills whose assertions need the "
                        "pending save provably un-committed")
    p.add_argument("--grace-s", type=float, default=None,
                   help="preemption grace budget in seconds (default: "
                        "$APEX_TPU_PREEMPTION_GRACE_S); the SIGTERM save "
                        "downgrades to finalize-pending or "
                        "skip-and-rely-on-last-verified when a full save "
                        "cannot fit (docs/resilience.md)")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO-2 optimizer (DistributedFusedAdam): Adam "
                        "moments + fp32 master sharded 1/dp over the dp "
                        "axis; checkpoints of this state reshard across a "
                        "dp-size change via the elastic restore")
    p.add_argument("--compression", default="none",
                   choices=["none", "int8", "fp8"],
                   help="quantized gradient collectives "
                        "(apex_tpu.parallel.compress, docs/parallel.md "
                        "'Compressed collectives'): the dp gradient sync "
                        "travels block-scaled int8/fp8 + fp32 scales with "
                        "an error-feedback residual carried in the "
                        "optimizer-state slot; found_inf consensus and "
                        "the master update stay exact")
    p.add_argument("--compression-block", type=int, default=128,
                   help="elements per fp32 scale block for --compression")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--journal", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="flight-recorder journaling "
                        "(apex_tpu.resilience.replay): per-step batch "
                        "ids/crc, chaos arms, lr_scale, and "
                        "loss/verdict/layer_rms fingerprints as "
                        "kind='journal' records + the "
                        "<save>/replay-journal.jsonl sidecar; every "
                        "checkpoint becomes a replay anchor and the "
                        "per-layer rms taps turn on. Default: on when "
                        "--save is set, RECORDING the current numerics "
                        "flags (--no-journal to disable); passing "
                        "--journal explicitly also PINS the "
                        "determinism_guard flags (matmul 'highest', x64 "
                        "off) for cross-setup stability")
    # resilience policy (apex_tpu.resilience; docs/resilience.md)
    p.add_argument("--spike-z", type=float, default=6.0,
                   help="loss z-score above the running EMA that counts as a spike")
    p.add_argument("--spike-warmup", type=int, default=10,
                   help="clean steps before spike detection arms")
    p.add_argument("--skip-budget", type=int, default=1,
                   help="consecutive anomalies answered by skipping the batch")
    p.add_argument("--rollback-budget", type=int, default=2,
                   help="further consecutive anomalies answered by rollback")
    p.add_argument("--snapshot-interval", type=int, default=10,
                   help="steps between in-memory rollback snapshots")
    p.add_argument("--snapshot-capacity", type=int, default=2,
                   help="rollback snapshots kept in host RAM")
    p.add_argument("--max-rollbacks", type=int, default=3,
                   help="rollbacks per run before halting")
    p.add_argument("--lr-dampen", type=float, default=0.5,
                   help="lr_scale multiplier applied on each rollback")
    p.add_argument("--anomaly-log", default=None,
                   help="jsonl anomaly log (default: <save>/anomalies.jsonl)")
    # telemetry (apex_tpu.monitor; docs/observability.md): metrics are
    # aggregated ON DEVICE in a MetricBag and fetched once per interval —
    # every host fetch stalls the dispatch pipeline, so per-step logging
    # would dominate small steps
    p.add_argument("--log-interval", type=int, default=5,
                   help="steps between metric records (and bag fetches)")
    p.add_argument("--metrics-jsonl", default=None,
                   help="write metric/anomaly/timer records to this jsonl")
    p.add_argument("--metrics-csv", default=None,
                   help="also write metric records to this CSV")
    p.add_argument("--tensorboard-dir", default=None,
                   help="also write scalars to TensorBoard (if importable)")
    p.add_argument("--profile-step", type=int, default=None,
                   help="capture a jax.profiler trace window at this step")
    p.add_argument("--profile-dir", default=None,
                   help="profiler capture dir (default: <save>/profiles)")
    p.add_argument("--profile-analyze", action="store_true",
                   help="after the run, analyze the profiler capture(s) "
                        "taken: per-step compute/collective/exposed/idle "
                        "breakdown + achieved bytes/s per mesh axis vs the "
                        "ledger prediction + device time by step phase, "
                        "Pallas kernel and module "
                        "(apex_tpu.monitor.xray.timeline; kind='profile' "
                        "records); writes the compiled step's text beside "
                        "the capture. Implies --profile-step 1 "
                        "when no capture was otherwise requested")
    p.add_argument("--step-deadline", type=float, default=None,
                   help="stall watchdog: flag a step exceeding this many "
                        "seconds (default: off). Arms the incident ladder "
                        "(apex_tpu.resilience.health): warn at the "
                        "deadline, forensic kind='incident' dump at "
                        "--stall-dump-after x deadline, and — only with "
                        "--stall-terminate-after set — coordinated "
                        "self-termination")
    p.add_argument("--stall-dump-after", type=float, default=2.0,
                   help="incident ladder: capture the forensic bundle at "
                        "this multiple of --step-deadline")
    p.add_argument("--stall-terminate-after", type=float, default=None,
                   help="incident ladder: self-terminate (exit code 43, "
                        "spans flushed, pending save tombstoned) at this "
                        "multiple of --step-deadline; a rerun with the "
                        "same --save resumes from the last verified step "
                        "(default: off — warn and dump only)")
    p.add_argument("--data-skip-budget", type=int, default=16,
                   help="batches whose host-side load may fail (skipped "
                        "and logged, surfaced as data_skipped in metrics "
                        "records) before the run fails loudly")
    # auto-remediation (apex_tpu.resilience.remediation;
    # docs/resilience.md "Auto-remediation"): the policy-driven
    # controller that turns detector findings into bounded recovery
    # actions — canary-verified quarantine, probation, readmit,
    # escalate-to-halt — with kind="remediation" records and the
    # exit-code contract a supervisor restarts on
    # (python -m apex_tpu.resilience.remediation --supervise)
    p.add_argument("--remediate", action="store_true",
                   help="arm the auto-remediation controller (requires "
                        "--save: the persisted plan, the replay journal "
                        "the canary re-executes, and the checkpoints "
                        "quarantine falls back to all live there); the "
                        "run exits 44 to request a restart (reduced "
                        "topology / readmit / post-preemption rejoin) "
                        "and 45 on escalate-to-halt")
    p.add_argument("--remediation-probation", type=int, default=8,
                   help="clean steps a quarantined/restarted incarnation "
                        "must run before the case closes (readmit)")
    p.add_argument("--remediation-max-restarts", type=int, default=4,
                   help="controller-driven restarts before "
                        "escalate-to-halt")
    p.add_argument("--remediation-verify",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="canary-verify findings before any quarantine "
                        "(--no-remediation-verify is the DELIBERATELY "
                        "BROKEN policy the chaos campaign's "
                        "false-positive pin exists to catch — drills "
                        "only)")
    p.add_argument("--fleet-interval", type=int, default=None,
                   help="run the live fleet-health check (straggler "
                        "robust-z + cross-host replicated-value "
                        "divergence) every N steps over the in-process "
                        "record window, emitting kind='fleet' records "
                        "(default: off)")
    # X-ray (apex_tpu.monitor.xray; docs/observability.md): static +
    # runtime introspection of the compiled step itself
    p.add_argument("--xray-report", action="store_true",
                   help="startup banner: XLA memory breakdown of the "
                        "compiled step vs device headroom (kind='memory' "
                        "record)")
    p.add_argument("--xray-hbm", action="store_true",
                   help="HBM x-ray (monitor.xray.hbm): analytic "
                        "per-device breakdown banner reconciled against "
                        "XLA's memory_analysis at startup, live "
                        "kind='memory' watermark records on the metrics "
                        "cadence, and kind='oom' forensics on resource "
                        "exhaustion")
    p.add_argument("--xray-comms", action="store_true",
                   help="startup banner + periodic kind='comms' records: "
                        "per-axis collective bytes/step and ICI roofline "
                        "from a ledger trace of the step")
    p.add_argument("--audit-donation", action="store_true",
                   help="verify the step's donate_argnums against XLA's "
                        "realized input/output aliasing "
                        "(apex_tpu.analysis) before training; emits "
                        "kind='analysis' records")
    p.add_argument("--audit-comms", action="store_true",
                   help="diff the optimized HLO's collectives against "
                        "the xray ledger's prediction (ghost-collective "
                        "differ, apex_tpu.analysis.hlo) before training; "
                        "emits kind='analysis' records")
    # fault injection (apex_tpu.resilience.chaos) — for tests and drills
    p.add_argument("--chaos-nan-steps", default="",
                   help="comma/range list of steps whose loss is NaN-poisoned")
    p.add_argument("--chaos-sigterm-step", type=int, default=None,
                   help="deliver a real SIGTERM after this step")
    p.add_argument("--chaos-hang-step", type=int, default=None,
                   help="wedge the host loop mid-step at this step (a "
                        "hung-collective stand-in that never returns; "
                        "only the --step-deadline incident ladder can "
                        "end the job)")
    p.add_argument("--chaos-slow-steps", default="",
                   help="comma/range list of steps delayed by "
                        "--chaos-slow-s (straggler injection)")
    p.add_argument("--chaos-slow-s", type=float, default=1.0,
                   help="artificial delay per --chaos-slow-steps step")
    p.add_argument("--chaos-corrupt-latest", default="none",
                   choices=["none", "bitflip", "truncate"],
                   help="corrupt the newest checkpoint BEFORE restoring")
    p.add_argument("--chaos-bitflip-step", type=int, default=None,
                   help="flip one low-mantissa bit of one live param "
                        "leaf in memory AFTER this step (silent "
                        "corruption: the sentinel misses it and the next "
                        "checkpoint faithfully saves it — only "
                        "'python -m apex_tpu.resilience.replay --bisect' "
                        "can pin it)")
    p.add_argument("--chaos-bitflip-bit", type=int, default=12,
                   help="bit index (from the LSB) for "
                        "--chaos-bitflip-step")
    return p.parse_args(argv)


def target_config(args, journal_on: bool):
    """The shared builder's recipe for these arguments — everything the
    compiled step depends on (apex_tpu/training/gpt_step.py)."""
    from apex_tpu.training import GPTTargetConfig

    sizes = dict(vocab=args.vocab, layers=args.layers, hidden=args.hidden,
                 heads=args.heads)
    model = None
    if args.arch_file:
        import json

        from apex_tpu.models.arch import described_model

        with open(args.arch_file) as f:
            sizes, model = described_model(
                json.load(f), layers_kept=args.layers_kept,
                experts_held=args.experts_held,
                first_expert=args.first_expert, vocab_rows=args.vocab_rows,
                mtp_loss_coeff=args.mtp_loss_coeff,
                router_bias_update_speed=args.router_bias_update_speed)
    return GPTTargetConfig(
        **sizes, model=model, seq_len=args.seq_len, tp=args.tp,
        sequence_parallel=args.sequence_parallel,
        micro_batch=args.micro_batch, global_batch=args.global_batch,
        lr=args.lr, seed=args.seed, zero=args.zero,
        compression=args.compression,
        compression_block=args.compression_block,
        spike_z=args.spike_z, spike_warmup=args.spike_warmup,
        skip_budget=args.skip_budget,
        rollback_budget=args.rollback_budget,
        collect_layer_rms=journal_on,
    )


def main(argv=None):
    """Train; ``argv`` (default ``sys.argv[1:]``) lets a driver such as
    ``chip_smoke.py`` run this very loop in-process."""
    args = parse_args(argv)
    # compiled programs persist across runs (apex_tpu/utils/compile_cache.py:
    # JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache)
    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from apex_tpu.data import (
        IndexedTokenDataset, LMDataset, MegatronPretrainingSampler,
        RobustBatches,
    )
    from apex_tpu.utils import AutoResume, Timers, step_annotation
    from apex_tpu import monitor, resilience
    from apex_tpu.monitor import goodput
    from apex_tpu.resilience import chaos
    from apex_tpu.resilience.replay import (
        FlightRecorder, batch_crc, journal_path,
    )
    from apex_tpu.resilience.replay.replayer import determinism_guard
    from apex_tpu.resilience.replay.targets import synthetic_corpus
    from apex_tpu.training import build_gpt_training

    # host half of the telemetry, FIRST: one router, every producer
    # (metric bag, timers, anomaly stream, goodput spans) emits the same
    # record schema through it, and creating it before any real setup
    # keeps the run-level ledger's `unattributed` bucket honest — wall
    # time before the first record is interpreter startup, nothing else
    sinks = [monitor.StdoutSink()]
    if args.metrics_jsonl:
        sinks.append(monitor.JsonlSink(args.metrics_jsonl))
    if args.metrics_csv:
        sinks.append(monitor.CsvSink(args.metrics_csv))
    if args.tensorboard_dir:
        tb = monitor.try_tensorboard_sink(args.tensorboard_dir)
        if tb is None:
            print("no TensorBoard writer importable; --tensorboard-dir ignored")
        else:
            sinks.append(tb)
    # in-process window of the stream so the end-of-run goodput summary
    # accounts THIS run without re-reading (or requiring) a jsonl file;
    # kinds-filtered so metrics/timer traffic doesn't evict the spans.
    # "memory" (the HBM x-ray's interval watermarks, light traffic) rides
    # in the same window so tests can read the records back in-process
    goodput_mem = monitor.MemorySink(kinds=("run", "span", "memory"))
    # unfiltered short window for the incident ladder's forensic bundle:
    # the record tail a kind="incident" dump quotes (what the run looked
    # like as it wedged — metrics, spans, anomalies alike). Only wired
    # when the ladder exists to read it; nobody else consumes it.
    incident_mem = (monitor.MemorySink(max_records=512)
                    if args.step_deadline else None)
    router = monitor.MetricRouter(
        sinks + [goodput_mem]
        + ([incident_mem] if incident_mem is not None else [])
    )

    # run-level goodput ledger (apex_tpu.monitor.goodput,
    # docs/observability.md "Goodput & fleet health"): this incarnation
    # announces itself with a kind="run" header — the run id is derived
    # from the --save path, so every restart of the same job joins into
    # ONE ledger — then every lifecycle phase (init, compile, data_wait,
    # step, ckpt_save/restore, rollback, stall, shutdown) emits a
    # kind="span" record the accountant partitions into goodput/badput.
    # set_router wires the library's own spans (AutoResume, rollback)
    # and arms the SIGTERM/atexit flush of in-flight spans. The devices
    # touch initializes the jax backend FIRST so the header resolves the
    # same host index as every later record — emitted earlier it would
    # say host 0 on every process and orphan non-zero hosts' spans.
    len(jax.devices())
    run_id = goodput.derive_run_id(args.save)
    run_rec = goodput.run_header(router, run_id, steps=args.steps)
    goodput.set_router(router)
    init_span = goodput.begin_span("init")

    # flight-recorder journaling (apex_tpu.resilience.replay): default on
    # when the run has the checkpoints replay anchors to. The
    # determinism_guard records the numerics flags (matmul precision,
    # x64) BEFORE any compile so the replayer can apply the identical
    # ones — and only PINS them when --journal was passed explicitly:
    # merely adding --save must never change a run's compiled numerics
    # (same-platform bitwise replay needs matching flags, not any
    # particular value).
    journal_on = (args.journal if args.journal is not None
                  else bool(args.save))
    guard_flags = (determinism_guard(pin=args.journal is True)
                   if journal_on else {})

    # the training step itself comes from the ONE shared builder the
    # replayer also uses (apex_tpu/training/gpt_step.py): identical
    # compiled computation by construction, not by code duplication
    tcfg = target_config(args, journal_on)
    training = build_gpt_training(tcfg)
    mesh, dp, num_micro = training.mesh, training.dp, training.num_micro
    train_step = training.train_step
    replicated = training.replicated
    ddp_compressed = training.ddp_compressed
    print(f"mesh: dp={dp} tp={args.tp} devices={len(jax.devices())}")

    prefix = args.corpus or synthetic_corpus(tcfg.vocab)
    lm = LMDataset(IndexedTokenDataset(prefix), seq_len=args.seq_len)

    recorder = None
    if journal_on:
        # sidecar next to the checkpoints when --save is set (flushed
        # with every manifest commit); kind="journal" records join the
        # router stream either way
        recorder = FlightRecorder(
            journal_path(args.save) if args.save else None, router=router
        )
        recorder.header(
            run_id, "gpt", config=tcfg.to_json(),
            corpus={"prefix": prefix,
                    **({} if args.corpus
                       else {"synthetic": {"vocab": tcfg.vocab,
                                           "n_tokens": 200_000}})},
            devices=len(jax.devices()), steps=args.steps, **guard_flags,
        )

    # model/optimizer/scaler/sentinel and the donated train_step all come
    # from the shared builder above (apex_tpu/training/gpt_step.py — the
    # --zero / --compression / sentinel semantics live there, where the
    # replayer rebuilds them identically)
    params, opt_state, scaler_state, sent_state = training.init_state()
    bag = training.init_bag()

    # analytic model FLOPs for MFU/throughput (docs/observability.md);
    # peak is None off-TPU unless APEX_TPU_PEAK_FLOPS pins it, and the
    # mfu field is then emitted as null rather than against a fake peak
    flops_per_token = monitor.gpt_flops_per_token(
        training.transformer_config, args.seq_len
    )
    tokens_per_step = args.global_batch * args.seq_len
    peak_flops = monitor.peak_flops_per_device()

    profile_dir = args.profile_dir or os.path.join(
        args.save if args.save else tempfile.gettempdir(), "profiles"
    )
    # router-backed: each completed capture emits its own kind="profile"
    # record (path/reason/end_step) without a hand-rolled callback
    trigger = monitor.ProfilerTrigger(profile_dir, window_steps=2,
                                      router=router)
    if args.profile_analyze and args.profile_step is None:
        # the analyzer needs a capture to chew on; step 1 skips the
        # compile-dominated step 0 so the window shows steady state
        args.profile_step = 1
    if args.profile_step is not None:
        trigger.request(step=args.profile_step)
    # the incident responder (--step-deadline) is created AFTER AutoResume
    # below: its terminate stage tombstones ar's pending save

    # chaos drill: corrupt the newest checkpoint BEFORE restore — the
    # verified restore must fall back to the previous intact step
    if args.save and args.chaos_corrupt_latest != "none":
        touched = chaos.corrupt_latest_checkpoint(
            args.save, mode=args.chaos_corrupt_latest
        )
        if touched:
            print(f"[chaos] corrupted newest checkpoint: {touched}")

    # --save enables BOTH periodic checkpoints and preemption-safe exit:
    # SIGTERM (preemptible TPU VMs send it before eviction) checkpoints the
    # current step and breaks the loop; a rerun with the same --save dir
    # resumes — from the newest CHECKSUM-VERIFIED step (torn/corrupt step
    # dirs are skipped; see apex_tpu.resilience.integrity).
    # mesh= routes a topology-changed restore through the elastic
    # resharder (8-chip checkpoint resumed on 4, dp-sharded ZeRO state
    # regrouped); grace_s= arms the deadline-budgeted termination save
    # journal= makes every AutoResume save a replay ANCHOR (journal
    # anchor record + sidecar fsync at the manifest commit), and the
    # termination/incident paths flush the sidecar so post-mortem replay
    # works after exit-43 and preemption, not just clean runs
    ar = (
        AutoResume(args.save, interval=args.save_interval,
                   keep_last_n=args.keep_last_n, mesh=mesh,
                   grace_s=args.grace_s,
                   background_finalize=args.background_finalize,
                   journal=recorder)
        if args.save else None
    )
    step0 = 0
    if ar is not None:
        try:
            step0, (params, opt_state, scaler_state, sent_state) = ar.restore(
                (params, opt_state, scaler_state, sent_state)
            )
        except ValueError as e:
            # a --save dir written by an older payload layout: train fresh
            # rather than crash (old checkpoints stay on disk untouched).
            # A refused elastic reshard is ElasticRestoreError — a
            # RuntimeError, deliberately NOT caught here: resuming fresh
            # over a refusal would silently discard the run
            print(f"checkpoint in {args.save} has an incompatible layout "
                  f"({e}); starting fresh")
        if step0 == 0 and ddp_compressed:
            # --compression newly enabled on an existing same-topology
            # checkpoint: the saved opt slot is the plain adam state
            # without the ef_residual wrapper, so the verified walk
            # found nothing restorable under the NEW structure. Retry
            # with the pre-compression target and start the advisory
            # residuals at zero instead of discarding the run (the
            # reshard path's zero-fill rule, applied here). A no-
            # checkpoint dir just returns 0 again — harmless.
            try:
                step0, (params, plain_opt, scaler_state, sent_state) = (
                    ar.restore((params, opt_state["opt"],
                                scaler_state, sent_state)))
            except ValueError:
                plain_opt = None  # genuinely incompatible: stay fresh
            if step0:
                opt_state = {"opt": plain_opt,
                             "ef_residual": opt_state["ef_residual"]}
                print("resumed a pre-compression checkpoint; "
                      "error-feedback residuals start at zero")
        if step0 == 0:
            from apex_tpu.utils.checkpoint import latest_step

            if latest_step(args.save) is not None:
                # checkpoints exist but none restored: most likely a
                # state-LAYOUT change across an upgrade (e.g. the ZeRO
                # state gained its ef_residual field) — the verified
                # walk logs per-step warnings, but a silent fresh start
                # on a long run deserves one loud line
                print(f"WARNING: checkpoints exist under {args.save} "
                      f"but none restored under the current state "
                      f"layout; training starts FRESH (a pre-upgrade "
                      f"state layout needs a migration — "
                      f"docs/resilience.md)")
        if step0:
            print(f"resumed from step {step0}")
    if recorder is not None:
        # the segment start: a fresh run's init state is reconstructable
        # from the seed (init=True anchor); a resumed run anchors on the
        # verified checkpoint it restored
        recorder.anchor(step0, init=(step0 == 0))

    # auto-remediation (apex_tpu.resilience.remediation): detector
    # records tap straight off the router (ControllerSink — fleet flags,
    # watchdog stalls, the sentinel's skip/rollback/halt trail), the
    # canary re-executes journaled segments through THIS process's own
    # compiled step (zero extra builds), and decisions come back as exit
    # codes the supervisor restarts on. Created after AutoResume/recorder
    # so it can adopt the persisted plan (a quarantine entering
    # probation, a supervisor-recorded incident exit).
    controller = None
    if args.remediate:
        if not args.save:
            raise SystemExit(
                "--remediate requires --save: the persisted remediation "
                "plan, the replay journal, and the quarantine fallback "
                "checkpoints all live in the save directory"
            )
        from apex_tpu.resilience import remediation
        canary = remediation.GPTCanary(
            journal_path(args.save), args.save, training=training, lm=lm,
            floor_step=step0,
        ) if recorder is not None else None
        # world_devices is the FULL topology (the controller contract:
        # what a readmit restores, the ordinal space state.excluded is
        # numbered in) — in a supervisor-relaunched reduced incarnation
        # the visible devices are world minus the quarantined ordinals,
        # so reconstruct the world from both
        _rstate = remediation.RemediationState.load(args.save)
        controller = remediation.RemediationController(
            policy=remediation.RemediationPolicy(
                probation_steps=args.remediation_probation,
                max_restarts=args.remediation_max_restarts,
                verify_before_quarantine=args.remediation_verify,
            ),
            router=router, save_dir=args.save,
            world_devices=len(jax.devices()) + len(_rstate.excluded),
            canary_fn=canary, state=_rstate, run_id=run_id,
        )
        router.add_sink(remediation.ControllerSink(controller))
        controller.adopt_pending(step0)

    # hung-job defense (apex_tpu.resilience.health, docs/resilience.md
    # "Incident response"): warn -> forensic kind="incident" dump ->
    # (opt-in) coordinated self-termination. Created here, STARTED after
    # the first completed step: the deadline is a steady-state bound, and
    # arming it across restore + trace + first-step compile would flag
    # every healthy run as stalled. The warn level is the PR-2 stall
    # record + span; the terminate level flushes interrupted spans,
    # tombstones ar's pending save, and exits 43 so a rerun with the
    # same --save elastic-restores the last VERIFIED step under the same
    # run id.
    responder = None
    if args.step_deadline:
        responder = resilience.health.IncidentResponder(
            args.step_deadline, router=router, window=incident_mem,
            trigger=trigger, autoresume=ar,
            dump_after=args.stall_dump_after,
            terminate_after=args.stall_terminate_after,
        )

    # live fleet health (--fleet-interval): the offline straggler /
    # replicated-value divergence math run in-job over a rolling window
    # (kind="fleet" records; single-host runs emit summaries only —
    # the verdicts need >= 2 hosts to be sound)
    fleet_mon = None
    if args.fleet_interval:
        fleet_win = monitor.MemorySink(
            max_records=4096, kinds=("span", "metrics")
        )
        router.add_sink(fleet_win)
        fleet_mon = goodput.LiveFleetMonitor(
            router, fleet_win, interval_steps=args.fleet_interval
        )

    # X-ray startup banners (apex_tpu.monitor.xray, docs/observability.md):
    # what the compiled step IS — collective traffic and HBM footprint —
    # before the first batch runs. The ledger trace is abstract
    # (eval_shape: milliseconds, no devices); the memory report pays a
    # real compile (see the NOTE below).
    batch_struct = jax.ShapeDtypeStruct(
        (num_micro, args.micro_batch * dp, args.seq_len), jnp.int32
    )
    scalar_struct = jax.ShapeDtypeStruct((), jnp.float32)
    step_args = (params, opt_state, scaler_state, sent_state, bag,
                 batch_struct, batch_struct, scalar_struct, scalar_struct)
    # aval-only mirror of step_args for anything that traces AFTER the
    # first real step: the concrete state leaves in step_args are donated
    # on the first call, and a post-run trace must not touch dead buffers
    step_structs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), step_args
    )
    comms_led = None
    if args.xray_comms:
        comms_led = monitor.xray.predict_comms(train_step, *step_args)
        print(comms_led.summary(), flush=True)
        for rec in comms_led.to_records(step=step0):
            router.emit(rec)
    if args.xray_report:
        # NOTE: this pays one extra compile of the step at startup — on
        # jax 0.4.x the AOT compile does not share the jit dispatch
        # cache (see xray.memory_report's docstring)
        report = monitor.xray.memory_report(train_step, *step_args)
        print(report.format(), flush=True)
        router.event("memory", step0, **report.fields())
    hbm_mon = None
    hbm_predicted = None
    if args.xray_hbm:
        # HBM x-ray (monitor.xray.hbm, docs/observability.md "HBM
        # x-ray"): the analytic ledger's closed-form per-device
        # breakdown first — an infeasible config is explained in
        # arithmetic before any compile — then XLA's own account of the
        # compiled step joined against it (pays the same extra AOT
        # compile --xray-report does; combine the flags freely, each
        # compile is independent)
        from apex_tpu.monitor.xray import hbm as xhbm

        hbm_predicted = xhbm.predict_train_memory(
            xhbm.TransformerDims.from_config(training.transformer_config),
            tp=args.tp,
            microbatch_size=args.micro_batch,
            seq_len=args.seq_len,
            optimizer=("distributed_fused_adam" if args.zero
                       else "fused_adam"),
            zero_axis_size=dp if args.zero else None,
            error_feedback=args.zero and args.compression != "none",
            grad_scaler=True,
            remat="none",
            compression_wire_dtype=(
                None if args.compression == "none"
                else {"int8": "int8", "fp8": "float8_e4m3fn"}[
                    args.compression]
            ),
            label="gpt-pretrain",
        )
        print(hbm_predicted.format(), flush=True)
        try:
            hbm_report = monitor.xray.memory_report(train_step, *step_args)
        except RuntimeError as e:
            # the flag exists to VERIFY; a backend with no memory
            # analysis must not print ok (the --audit-comms hardening)
            raise SystemExit(f"hbm x-ray failed: {e}")
        achieved = hbm_report.total_bytes
        print(
            f"hbm x-ray: predicted peak "
            f"{hbm_predicted.peak_bytes / 2**20:.1f} MiB vs compiled "
            f"total {achieved / 2**20:.1f} MiB "
            f"(x{achieved / max(1, hbm_predicted.peak_bytes):.2f})",
            flush=True,
        )
        router.event(
            "memory", step0, scope="compiled",
            predicted_peak_bytes=hbm_predicted.peak_bytes,
            **hbm_report.fields(),
        )
        hbm_mon = xhbm.HbmWatermarkMonitor(
            router, interval_steps=args.log_interval,
            predicted=hbm_predicted,
        )
    audit_lowered = audit_compiled = audit_module = None
    if args.audit_donation or args.audit_comms:
        # ONE AOT compile + ONE HLO text/parse shared by both audits
        # (the ctx.aot()/ctx.hlo_module() pattern the CLI gate uses) —
        # each flag alone would otherwise pay its own multi-second
        # .lower().compile() and re-serialize the optimized HLO
        from apex_tpu.analysis.hlo import parse_hlo_module

        audit_lowered = train_step.lower(*step_args)
        audit_compiled = audit_lowered.compile()
        try:
            audit_module = parse_hlo_module(audit_compiled)
        except ValueError:
            pass  # each audit re-derives and reports unverifiable
    if args.audit_donation:
        # static donation audit (apex_tpu.analysis, docs/analysis.md):
        # the declared donate_argnums vs the aliases XLA actually
        # realized, plus large buffers that could be donated but aren't.
        from apex_tpu.analysis import repo_allowlist
        from apex_tpu.analysis.donation import audit_donation

        fins = audit_donation(
            train_step, *step_args,
            arg_names=("params", "opt_state", "scaler_state", "sent_state",
                       "bag", "tokens", "labels", "inject_nan", "lr_scale"),
            target="gpt-pretrain",
            lowered=audit_lowered, compiled=audit_compiled,
            hlo_module=audit_module,
        )
        audit = repo_allowlist().apply(fins, check_stale=False)
        for rec in audit.to_records(step=step0):
            router.emit(rec)
        # an 'unverifiable' outcome (auditor could not map HLO params to
        # input leaves) is info-severity but must NOT print ok: the flag
        # exists to VERIFY, and a vacuous pass would hide a pruned arg
        unverifiable = [
            f for f in fins if f.rule == "donation.unverifiable"
        ]
        if audit.ok and not unverifiable:
            print("donation audit: ok (params/opt/scaler/sentinel alias "
                  "in place)", flush=True)
        else:
            print(audit.format(verbose=True), flush=True)
            raise SystemExit("donation audit failed")
    if args.audit_comms:
        # ghost-collective differ (apex_tpu.analysis.hlo, docs/analysis.md):
        # every collective XLA actually emitted must match a ledger
        # prediction — resharding leaks and transpose-synthesized traffic
        # surface here. Reuses --audit-donation's compile.
        from apex_tpu.analysis import repo_allowlist
        from apex_tpu.analysis.hlo import audit_comms

        fins = audit_comms(
            train_step, *step_args, mesh=mesh, target="gpt-pretrain",
            compiled=audit_compiled, module=audit_module,
        )
        audit = repo_allowlist().apply(fins, check_stale=False)
        for rec in audit.to_records(step=step0):
            router.emit(rec)
        # an 'unverifiable' outcome (no mesh / unparseable HLO) is
        # info-severity but must NOT print ok: the flag exists to VERIFY,
        # same hardening rule as --audit-donation above
        unverifiable = [f for f in fins if f.rule == "comms.unverifiable"]
        if audit.ok and not unverifiable:
            print("comms audit: ok (emitted collectives match the ledger "
                  "prediction)", flush=True)
        else:
            print(audit.format(verbose=True), flush=True)
            # reshard findings carry a concrete prescription (the entry
            # param whose missing spec makes the partitioner move data)
            for f in fins:
                if f.rule == "comms.reshard" and f.data.get("suggestion"):
                    print(f"  fix: {f.data['suggestion']}", flush=True)
            raise SystemExit("comms audit failed")
    # warm the interval-emission path's eager host ops (bag pack/reset)
    # NOW: their one-off compiles must land before the recompile
    # sentinel arms, and on a RESUMED run the first interval boundary
    # can be many steps past step0 — well after warmup
    monitor.read_bag(bag)
    bag = jax.device_put(monitor.reset_bag(bag), replicated)
    # recompile sentinel: always on — a silent post-warmup recompile is
    # the classic 10x step-time killer and costs nothing to watch for
    compile_watcher = monitor.xray.CompileWatcher(router=router)

    # host half of the resilience loop: snapshot ring + escalation policy
    # (skip -> rollback + LR dampen -> halt) + per-run anomaly log
    mgr = resilience.ResilienceManager(
        buffer=resilience.RollbackBuffer(
            capacity=args.snapshot_capacity, interval=args.snapshot_interval
        ),
        policy=resilience.EscalationPolicy(
            max_rollbacks=args.max_rollbacks, lr_dampen=args.lr_dampen
        ),
        log_path=args.anomaly_log
        or (os.path.join(args.save, "anomalies.jsonl") if args.save else None),
        router=router,  # anomalies join the metric stream, same schema
    )
    plan = chaos.FaultPlan(
        nan_steps=args.chaos_nan_steps,
        sigterm_steps=(
            {args.chaos_sigterm_step}
            if args.chaos_sigterm_step is not None else frozenset()
        ),
        hang_steps=(
            {args.chaos_hang_step}
            if args.chaos_hang_step is not None else frozenset()
        ),
        slow_steps=args.chaos_slow_steps,
        slow_s=args.chaos_slow_s,
        bitflip_steps=(
            {args.chaos_bitflip_step}
            if args.chaos_bitflip_step is not None else frozenset()
        ),
        bitflip_bit=args.chaos_bitflip_bit,
    )

    # the sampler's own resume mechanism picks the data stream up exactly
    # where the saved (or rolled-back-to) run left off
    def make_iter(start_step):
        return iter(MegatronPretrainingSampler(
            total_samples=len(lm),
            consumed_samples=start_step * args.global_batch,
            local_minibatch_size=args.global_batch,  # host batch; dp shards
            data_parallel_rank=0,                    # on device
            data_parallel_size=1,
        ))

    timers = Timers(write_fn=router.timer_write_fn)
    it = make_iter(step0)
    # bounded skip-and-log around the host-side load (apex_tpu.data.
    # robust): a flaky batch is skipped and counted (data_skipped in the
    # metrics records); blowing --data-skip-budget raises — silent
    # infinite skipping is the failure mode, not the fix. Reads `it`
    # late-bound so the rollback path's iterator rewind stays effective.
    # The loader surfaces the sample ids it ACTUALLY consumed (last_ids)
    # so the journal records them per step: a skipped batch shifts every
    # subsequent one, and replay must fetch the journaled ids, not re-run
    # the skip history.
    last_ids = []

    def load_batch():
        ids = list(next(it))
        last_ids[:] = ids
        return lm.batch(ids)

    batches = RobustBatches(load_batch, max_skips=args.data_skip_budget)
    # seed the ring so an anomaly before the first cadence point can still
    # roll back instead of escalating straight to halt
    mgr.buffer.snapshot(step0, (params, opt_state, scaler_state, sent_state))
    init_span.close()  # everything before the loop is init (or a nested
    # higher-priority phase: ckpt_restore from ar.restore above)
    exit_code = 0
    steps_run = 0
    steps_since_emit = 0
    last_emit_t = time.perf_counter()
    step_i = step0
    # OOM forensics (monitor.xray.hbm.oom): the step call is the blessed
    # execute boundary — a RESOURCE_EXHAUSTED surfaces as ONE kind="oom"
    # incident bundle (analytic breakdown + ranked knob suggestions) and
    # re-raises; inert when --xray-hbm is off
    if hbm_mon is not None:
        from apex_tpu.monitor.xray.hbm.oom import oom_guard as _oom_guard

        def step_oom_guard(step):
            return _oom_guard(router, step, breakdown=hbm_predicted)
    else:
        def step_oom_guard(step):
            return contextlib.nullcontext()
    while step_i < args.steps:
        # host blocked on the input pipeline = data_wait badput; the
        # robust loader skips-and-counts flaky loads inside the span
        with goodput.span("data_wait", step=step_i):
            x0, y0 = batches()
            x = x0.reshape(num_micro, args.micro_batch * dp, args.seq_len)
            y = y0.reshape(num_micro, args.micro_batch * dp, args.seq_len)
        batch_ids = list(last_ids)
        # the crc fingerprints the batch CONTENT (journal.batch_crc): a
        # replay re-fetching these ids must see these bytes
        crc = batch_crc(x0, y0) if recorder is not None else None
        nan_armed = plan.take_nan(step_i)
        lr_scale_now = mgr.lr_scale
        trigger.maybe_start(step_i)
        # run-level span: the first call is compile-dominated (no AOT
        # split exists for the jit step), so it books as compile badput;
        # later iterations are the goodput numerator. The barrier inside
        # step_annotation makes the span cover completed device work.
        with goodput.span("compile" if steps_run == 0 else "step",
                          step=step_i), step_oom_guard(step_i):
            # step marker: every profiler window carries a span the
            # timeline analyzer can segment on; the barrier inside keeps
            # the step's device tail out of the next step's span
            with step_annotation(step_i):
                timers("step").start()
                out = train_step(
                    params, opt_state, scaler_state, sent_state, bag,
                    jnp.asarray(x), jnp.asarray(y),
                    jnp.asarray(nan_armed, jnp.float32),
                    jnp.asarray(lr_scale_now, jnp.float32),
                )
                # journaling mode appends the per-layer rms vector to the
                # step outputs (training.build_gpt_training)
                if journal_on:
                    (params, opt_state, scaler_state, sent_state, bag,
                     loss, verdict, layer_rms) = out
                else:
                    (params, opt_state, scaler_state, sent_state, bag,
                     loss, verdict) = out
                    layer_rms = None
                # the loss/verdict fetch below is the step's host sync
                # point, so the profiler window closes on completed work
                timers("step").stop(barrier_on=loss)
            if responder is not None and steps_run == 0:
                # compile is behind us; deadline arms now — and BEFORE
                # the first chaos-injection opportunity below, so a
                # wedge at the very first executed step is still
                # answered by the ladder instead of hanging unwatched
                responder.start()
            # chaos: straggler delay / host-loop wedge, injected INSIDE
            # the step span so (a) the slow step inflates exactly the
            # span the stall warn flags and (b) a wedge leaves the span
            # OPEN — the incident terminate's teardown flushes it
            # interrupted=True, and the phase="incident" span (which
            # outranks "step") claims the dead time
            plan.maybe_slow(step_i)
            plan.maybe_hang(step_i)
        steps_run += 1
        steps_since_emit += 1
        if responder is not None:
            responder.beat(step_i)
        verdict_code = int(verdict)  # ONE fetch; reused below
        loss_f = float(loss)         # likewise: resolve + journal share it
        trigger.on_verdict(step_i, verdict_code)
        trigger.maybe_stop(step_i)
        if recorder is not None:
            # everything a replay needs to re-execute THIS step (batch
            # ids + content crc, chaos arm, lr damping) and the output
            # fingerprints it will be compared against; the sequential
            # sampler yields contiguous ranges, stored compactly
            contiguous = batch_ids == list(
                range(batch_ids[0], batch_ids[-1] + 1))
            recorder.step(
                step_i,
                batch=([batch_ids[0], batch_ids[-1] + 1]
                       if contiguous else None),
                batch_ids=(None if contiguous else batch_ids),
                batch_crc=crc, inject_nan=nan_armed,
                lr_scale=lr_scale_now, loss=loss_f, verdict=verdict_code,
                loss_scale=float(scaler_state.scale),
                layer_rms=np.asarray(layer_rms),
                data_skipped=batches.skipped,
            )
        # chaos: silent in-memory corruption, applied AFTER the step so
        # the next checkpoint faithfully saves it (bitflip_leaf): the
        # sentinel stays quiet, the run completes — only the replay
        # bisector can pin it to this boundary and this leaf
        params, flip_info = plan.maybe_bitflip(step_i, params)
        if flip_info is not None:
            print(f"[chaos] bit-flipped {flip_info['path']}"
                  f"[{flip_info['element']}] bit {flip_info['bit']}")
            if recorder is not None:
                recorder.event(step_i, "bitflip_injected", **flip_info)
        state = (params, opt_state, scaler_state, sent_state)
        action = mgr.resolve(step_i, verdict_code, loss=loss_f)
        if action == "halt":
            if responder is not None:
                # the final durable save below is not a step: a long
                # checkpoint must not be escalated as a wedge (and the
                # terminate level must never tombstone it)
                responder.stop()
            # save the newest KNOWN-GOOD state, not the possibly-corrupt
            # live one, then stop: the anomaly outlived every budget
            good_step, good_state = (
                mgr.buffer.rollback() if len(mgr.buffer) else (step_i, state)
            )
            if args.save:
                if ar is not None:
                    # an interval save may still be in flight to the same
                    # directory; finalize it before writing (its retention
                    # sweep would otherwise race the async write's tmp dir)
                    ar.finalize()
                resilience.save_checkpoint_verified(
                    args.save, good_step, good_state,
                    keep_last_n=args.keep_last_n,
                )
            if recorder is not None:
                # the journaled trajectory ends here (the replayer
                # refuses to replay across a halt)
                recorder.event(step_i, "halt", good_step=good_step)
            if controller is not None:
                # the halt record (via ControllerSink) opened an
                # escalation case; its terminal verdict + the
                # REMEDIATION_HALT code tell the supervisor NOT to
                # restart a fault the ladder already failed to heal
                decision = controller.process(step_i)
                if decision is not None:
                    exit_code = decision.exit_code
            print(f"halting at step {step_i}: anomaly persisted; "
                  f"checkpointed known-good step {good_step}")
            break
        if action == "rollback":
            rolled_from = step_i
            step_i, (params, opt_state, scaler_state, sent_state) = (
                mgr.do_rollback()
            )
            it = make_iter(step_i)
            if recorder is not None:
                # rollback restores the in-memory snapshot ring — a
                # non-replayable break (journal.breaks_in); the replayer
                # refuses segments spanning it instead of diverging
                recorder.event(rolled_from, "rollback", to_step=step_i)
            print(f"rolled back to step {step_i} "
                  f"(lr_scale {mgr.lr_scale:.3f})")
            continue
        if action == "skip":
            print(f"anomalous step {step_i}: update skipped "
                  f"(loss {loss_f:.4f})")
        else:
            mgr.observe_good(step_i + 1, state)
        if controller is not None and verdict_code == 0:
            # probation / observation counters: a clean verdict-OK step
            # advances every open case toward its closure (readmit /
            # recover)
            controller.on_clean_step(step_i)
        if step_i % args.log_interval == 0 or step_i == args.steps - 1:
            # ONE device-to-host metrics fetch per interval (the packed
            # MetricBag vector); everything else in the record is host math
            if hbm_mon is not None:
                # kind="memory" watermark record on the metrics cadence
                # (device.memory_stats via the blessed hbm.live probe;
                # CPU reports none — fields stay None, never faked)
                hbm_mon.sample(step_i)
            vals = monitor.read_bag(bag)
            secs = max(time.perf_counter() - last_emit_t, 1e-9)
            sec_per_step = secs / steps_since_emit
            router.metrics(
                step_i,
                **vals,
                tokens_per_s=monitor.tokens_per_second(
                    tokens_per_step * steps_since_emit, secs
                ),
                mfu=monitor.mfu(
                    monitor.training_flops_per_step(
                        flops_per_token, tokens_per_step
                    ),
                    sec_per_step,
                    num_devices=len(jax.devices()),
                    peak_flops=peak_flops,
                ),
                step_ms=1000.0 * sec_per_step,
                # MetricBag-adjacent HOST metric: batches lost to the
                # bounded skip-and-log loader this run (data/robust.py)
                data_skipped=batches.skipped,
                # remediation gauges (probation steps left, open cases);
                # both in CsvSink.TOLERATED_EXTRA_KEYS so frozen-header
                # CSV resumes survive the schema growth
                **(controller.metrics_fields()
                   if controller is not None else {}),
                # HBM watermark gauges (peak_hbm_bytes/hbm_utilization);
                # empty on CPU, and both in CsvSink.TOLERATED_EXTRA_KEYS
                # like the remediation gauges above
                **(hbm_mon.metrics_fields()
                   if hbm_mon is not None else {}),
            )
            # interval-mean step timer as a kind='timer' record; reset=True
            # (the write-parity fix) so each write covers ITS interval only
            timers.write(["step"], step_i, normalizer=steps_since_emit)
            if comms_led is not None:
                # periodic comms records: the traced-step totals restamped
                # at this step, so a jsonl tailer can join comms with
                # metrics without replaying the startup banner
                for rec in comms_led.to_records(step=step_i):
                    router.emit(rec)
            bag = jax.device_put(monitor.reset_bag(bag), replicated)
            steps_since_emit = 0
            last_emit_t = time.perf_counter()
        if fleet_mon is not None:
            fleet_mon.maybe_check(step_i)
        plan.maybe_sigterm(step_i)
        if (responder is not None and ar is not None
                and ar.termination_signaled):
            # stand the dog down BEFORE ar.step's blocking termination
            # save: a minutes-long durable save is not a wedged step,
            # and the terminate level must not tombstone the very
            # checkpoint the grace-budget decision chose to write.
            # (Host-local hint only — on a multi-host mesh a host whose
            # signal has not arrived yet keeps its dog armed through the
            # consensus; deadline >> save time remains the safe config.)
            responder.stop()
        if ar is not None and ar.step(step_i + 1, state):
            if ar.termination_decision == "save":
                print(f"termination checkpoint at step {step_i + 1}; exiting")
            else:
                # the grace budget could not fit a fresh save: the
                # deadline decision downgraded (finalize-pending or
                # skip-and-rely-on-last-verified) — say so, never claim
                # a checkpoint that was not committed
                print(f"termination at step {step_i + 1}: "
                      f"{ar.termination_decision} (grace budget); exiting")
            if controller is not None:
                # under a supervisor a preemption is a RESTART, not an
                # ending: persist the case, exit 44, rejoin on relaunch
                decision = controller.on_preemption(step_i)
                exit_code = decision.exit_code
                print(f"[remediation] {decision.reason} "
                      f"(exit {decision.exit_code})")
            break
        if controller is not None:
            anchor_due = bool(
                ar is not None and args.save_interval
                and (step_i + 1) % args.save_interval == 0
            )
            # stand the dog down around the controller's own work (the
            # halt-save idiom above): a canary replay is minutes of
            # legitimate host time, and a watchdog that flags its own
            # remediation layer as a stall would feed the controller a
            # spurious case
            fence = responder is not None and (
                anchor_due or controller.has_pending
            )
            if fence:
                responder.stop()
            if anchor_due:
                # a checkpoint anchor just landed: commit it (the canary
                # can only audit VERIFIED anchors — at run end there is
                # no next anchor to catch up on) and run the periodic
                # canary audit; the replay cost books as
                # phase="remediation" badput
                ar.finalize()
                controller.on_anchor(step_i + 1)
            decision = controller.process(step_i)
            if decision is None and fence:
                responder.start()
            if decision is not None:
                # act on the controller's verdict: flush the durable
                # state (the journal sidecar + any pending save) and
                # hand the supervisor the exit code + new topology
                if ar is not None:
                    ar.finalize()
                if recorder is not None:
                    recorder.flush()
                exit_code = decision.exit_code
                print(f"[remediation] {decision.reason} "
                      f"(exit {decision.exit_code}, "
                      f"devices {decision.device_count}, "
                      f"restore step {decision.restore_step})")
                break
        # compile accounting LAST in the iteration, so every first-use
        # host-side compile (the interval path is warmed before the
        # loop; AutoResume's consensus reduce builds lazily on its first
        # ar.step) lands in the FIRST iteration's bucket — warmup, not a
        # recompile warning
        compile_watcher.on_step(step_i)
        step_i += 1
    # everything after the loop is shutdown badput (final saves nested
    # inside book as ckpt_save — priority order, accountant.py)
    shutdown_span = goodput.begin_span("shutdown", step=step_i)
    if mgr.events:
        print(f"anomalies this run: {len(mgr.events)} "
              f"(rollbacks {mgr.rollbacks_used}, lr_scale {mgr.lr_scale:.3f})")
    if controller is not None:
        if exit_code == 0:
            # the run completed: close the observation/probation cases
            # that saw clean recovery (terminal kind="remediation"
            # verdicts); anything left open persists for the next
            # incarnation
            controller.run_end(step_i)
        closed = controller.state.history
        if closed or controller.open_cases:
            print(f"[remediation] {len(closed)} case(s) closed "
                  f"({[(c['kind'], c['verdict']) for c in closed]}), "
                  f"{len(controller.open_cases)} open")
    router.event(
        "summary", step_i, steps_run=steps_run, anomalies=len(mgr.events),
        rollbacks=mgr.rollbacks_used, lr_scale=mgr.lr_scale,
        profiles=len(trigger.captures),
    )
    if responder is not None:
        responder.stop()
    trigger.close()  # abort any capture still open (end of run)
    if args.profile_analyze:
        # device-time timeline of the capture(s) just taken
        # (apex_tpu.monitor.xray.timeline, docs/observability.md#timeline):
        # per-step compute/collective/exposed/idle partition segmented on
        # the step_annotation markers above, and measured per-axis
        # collective seconds joined to the ledger's predicted bytes.
        # Blanket-guarded (ProfilerTrigger's contract: losing a trace
        # must not lose the run) — a torn/truncated capture or a join
        # failure here must not skip ar.close()'s manifest commit below
        try:
            from apex_tpu.monitor.xray import timeline

            if audit_module is None:
                # the bandwidth join matches trace events to HLO
                # instruction names — reuse the audits' parsed module
                # when a --audit-* flag already paid the compile, else
                # pay one AOT compile here (the --xray-report cost note
                # applies)
                from apex_tpu.analysis.hlo import parse_hlo_module

                try:
                    audit_module = parse_hlo_module(
                        train_step.lower(*step_structs).compile()
                    )
                except (ValueError, TypeError) as e:
                    print(f"profile analyze: HLO module unavailable ({e}); "
                          f"bandwidth join skipped")
            led = (comms_led if comms_led is not None
                   else monitor.xray.predict_comms(train_step, *step_structs))
            bw = monitor.xray.ici_bandwidth_per_device()
            if not trigger.captures:
                print("profile analyze: no completed capture to analyze "
                      "(the run must continue window-steps past the capture "
                      "start)")
            for cap in trigger.captures:
                if audit_module is not None and audit_module.text:
                    # the compiled step beside its capture: an operator
                    # holds both halves of the scope join (device time by
                    # phase, kernel and module) and can re-read them with
                    # python -m apex_tpu.monitor.xray.timeline <dir> --hlo
                    hlo_path = os.path.join(cap["path"], "step.hlo.txt")
                    with open(hlo_path, "w") as f:
                        f.write(audit_module.text)
                    print(f"profile analyze: compiled step written to "
                          f"{hlo_path}")
                try:
                    report = timeline.analyze_logdir(
                        cap["path"], module=audit_module, mesh=mesh,
                        ledger=led, ici_bandwidth=bw,
                    )
                except (FileNotFoundError, ValueError) as e:
                    print(f"profile analyze: {cap['path']}: {e}")
                    continue
                print(f"profile timeline ({cap['path']}):")
                print(report.summary(), flush=True)
                for rec in report.to_records():
                    router.emit(rec)
        except Exception as e:
            print(f"profile analyze: failed ({e!r}); training results "
                  f"unaffected")
    if ar is not None:
        ar.close()  # finalize any in-flight interval save (manifest commit)
    if recorder is not None:
        recorder.close()  # fsync the journal sidecar with the run's end
    # run-level goodput summary (docs/observability.md "Goodput & fleet
    # health"): replay this run's own record window into the
    # productive/badput partition and land it in the SAME stream — the
    # identity productive + Σ badput + unattributed == wall holds exactly
    # on the emitted record. Multi-incarnation jobs re-account the full
    # jsonl offline: python -m apex_tpu.monitor.goodput <jsonl>
    shutdown_span.close()
    goodput.set_router(None)  # later spans (none expected) drop cleanly
    recs = list(goodput_mem.records)
    if not recs or recs[0] is not run_rec:
        # the bounded window evicted the run header (very long run):
        # re-pin it so the run-id join still holds — the evicted early
        # spans under-report badput here, but the jsonl is the durable
        # record and the offline CLI accounts it in full
        recs = [run_rec] + recs
    report = goodput.account(recs, run_id=run_id)
    print(report.summary(), flush=True)
    router.event("goodput", step_i, **report.fields())
    if hbm_mon is not None:
        # achieved-vs-predicted closing banner (None = CPU, not zero)
        hs = hbm_mon.summary()
        fmt = lambda b: ("n/a" if b is None else f"{b / 2**20:.1f} MiB")  # noqa: E731
        util = ("n/a" if hs["utilization"] is None
                else f"{hs['utilization']:.2f}")
        print(
            f"hbm x-ray: predicted peak "
            f"{fmt(hs['predicted_peak_bytes'])}, achieved "
            f"{fmt(hs['achieved_peak_bytes'])}, utilization {util}, "
            f"headroom breaches {hs['breaches']}",
            flush=True,
        )
    router.close()
    # the remediation exit-code contract (resilience/exit_codes.py): 0
    # done, 44 restart-me-with-the-persisted-plan, 45 escalated halt —
    # what `python -m apex_tpu.resilience.remediation --supervise`
    # branches on
    return exit_code


if __name__ == "__main__":
    raise SystemExit(main())
