"""Chaos campaigns: prove the policy table against fault COMBINATIONS.

Single-fault drills (the chaos flags the examples grew over PRs 1–12)
prove each recovery path in isolation; production faults arrive in
sequences — a straggler while a silent bit flip is still latent, a
SIGTERM mid-probation. This module runs SEEDED RANDOMIZED fault
sequences against the real GPT target through the real remediation
controller, entirely in-process on the virtual 8-device topology:

- :func:`random_sequence` draws a fault set (distinct kinds from
  ``nan``/``slow``/``hang``/``bitflip``/``sigterm`` at distinct steps,
  seeded ``random.Random`` — reproducible by construction);
- :func:`run_sequence` executes it: a miniature training loop (the
  GPT example's journaling/AutoResume/escalation wiring without its
  CLI shell) under an in-process supervisor that restarts incarnations
  on the controller's exit codes, rebuilding the training on the
  reduced topology through ``GPTTargetConfig.max_devices`` (the
  elastic-selftest sub-mesh trick) and elastic-restoring through
  ``AutoResume(mesh=)``;
- :func:`check_invariants` judges the outcome: the goodput partition
  identity re-adds ``==`` across every incarnation, every fault maps
  to EXACTLY ONE terminal ``kind="remediation"`` verdict, no
  quarantine happened without verified evidence (the false-positive
  pin — this is the invariant a deliberately broken
  ``verify_before_quarantine=False`` policy trips), and the final loss
  pins to an uninterrupted reference;
- :func:`minimize_failing` shrinks a failing sequence to a 1-minimal
  reproducer (drop-one-fault ddmin), so a policy regression reports
  "these two faults in this order" instead of "seed 17 failed".

The in-process hang is BOUNDED (``FaultPlan.hang_timeout_s``) and the
incarnation ends with the incident exit code after the watchdog's
forensic dump fires — the true ``os._exit(43)`` kill path is pinned by
the subprocess drills in tests/test_health.py; a campaign that
actually wedged or killed its own process could not run 20 sequences.
"""

import dataclasses
import logging
import os
import random
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from apex_tpu.resilience.exit_codes import (
    ExitCode,
    RESTARTABLE_EXIT_CODES,
)
from apex_tpu.resilience.remediation.policy import RemediationPolicy
from apex_tpu.resilience.remediation.state import RemediationState

logger = logging.getLogger("apex_tpu.resilience.remediation")

__all__ = [
    "FAULT_KINDS",
    "FaultEvent",
    "SequenceResult",
    "TrainingCache",
    "random_sequence",
    "run_sequence",
    "check_invariants",
    "minimize_failing",
    "run_campaign",
]

#: the fault vocabulary a campaign draws from
FAULT_KINDS = ("nan", "slow", "hang", "bitflip", "sigterm")

#: which terminal (finding, verdict) pairs may account for each fault
#: kind — the bipartite side of the one-terminal-verdict-per-fault
#: invariant. A ``bitflip`` may be caught by the periodic canary audit
#: (an ``sdc`` case) or ride a straggler/stall case whose canary
#: confirmation found the corruption first; either way its terminal is
#: the quarantine's ``readmitted`` (or ``halted`` when budgets ran
#: out). A ``slow`` that the canary cleared is ``cleared``; one closed
#: by clean-step observation is ``recovered``.
FAULT_TERMINALS: Dict[str, frozenset] = {
    "nan": frozenset({("sentinel", "recovered")}),
    "slow": frozenset({
        ("stall", "cleared"), ("stall", "recovered"),
        ("straggler", "cleared"), ("straggler", "recovered"),
    }),
    "hang": frozenset({("incident", "recovered")}),
    "bitflip": frozenset({
        ("sdc", "readmitted"), ("sdc", "halted"),
        ("stall", "readmitted"), ("straggler", "readmitted"),
        ("corruption", "readmitted"),
    }),
    "sigterm": frozenset({("preemption", "recovered")}),
}


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One scheduled fault."""

    kind: str
    step: int


def random_sequence(seed: int, steps: int = 8,
                    kinds: Sequence[str] = FAULT_KINDS,
                    max_faults: int = 3) -> List[FaultEvent]:
    """A seeded fault sequence: 1..max_faults DISTINCT kinds at distinct
    steps in [1, steps-2].

    Distinct kinds keep the fault→terminal mapping checkable (two
    stragglers would legitimately share one case — dedup by design);
    a ``bitflip`` always takes the LARGEST drawn step so the canary
    verifications that earlier faults trigger replay the pre-flip
    segments (still clean) and the corruption is attributed to its own
    detection, not smeared into an earlier case's evidence.
    """
    rng = random.Random(seed)
    n = rng.randint(1, min(max_faults, len(kinds)))
    chosen = rng.sample(list(kinds), n)
    lo, hi = 1, max(steps - 2, 1)
    avail = list(range(lo, hi + 1))
    rng.shuffle(avail)
    picked = sorted(avail[:len(chosen)])
    events: List[FaultEvent] = []
    if "bitflip" in chosen:
        events.append(FaultEvent("bitflip", picked[-1]))
        picked = picked[:-1]
        chosen = [k for k in chosen if k != "bitflip"]
    for kind, step in zip(chosen, picked):
        events.append(FaultEvent(kind, step))
    return sorted(events, key=lambda e: e.step)


#: the campaign target: tiny enough that one step is sub-second on the
#: CPU mesh, real enough that every remediation surface (journal,
#: anchors, sentinel, escalation, elastic reshard) is the production
#: code path. global_batch=8 divides every dp in {8, 4, 2, 1}.
def campaign_config(**overrides):
    from apex_tpu.training import GPTTargetConfig

    base = dict(
        vocab=64, seq_len=16, layers=2, hidden=32, heads=4, tp=1,
        micro_batch=1, global_batch=8, spike_warmup=4,
        collect_layer_rms=True,
    )
    base.update(overrides)
    return GPTTargetConfig(**base)


class TrainingCache:
    """One built training per device count (module docstring): the
    compiled step is the expensive half of an incarnation, and fault
    sequences only vary host-side inputs, so 20 sequences pay for at
    most two builds (full + quarantined topology)."""

    def __init__(self, base_cfg):
        self.base_cfg = base_cfg
        self._built: Dict[int, Tuple] = {}

    def get(self, device_count: int):
        """(cfg, training) for ``device_count`` devices."""
        if device_count not in self._built:
            from apex_tpu.training import build_gpt_training

            cfg = dataclasses.replace(
                self.base_cfg, max_devices=device_count
            )
            logger.warning(
                "campaign: building the %d-device training (cached for "
                "the rest of the campaign)", device_count,
            )
            self._built[device_count] = (cfg, build_gpt_training(cfg))
        return self._built[device_count]


@dataclasses.dataclass
class SequenceResult:
    """One executed sequence's full evidence."""

    faults: List[FaultEvent]
    run_id: str
    outcome: str                     # "completed"|"halted"|"failed..."|...
    incarnations: List[dict]
    records: List[dict]              # the whole record stream
    remediation: List[dict]          # the kind="remediation" slice
    losses: Dict[int, float]         # step -> loss (last execution wins)

    @property
    def terminals(self) -> List[dict]:
        return [r for r in self.remediation if r.get("terminal")]


def _run_incarnation(training, cfg, lm, prefix, workdir, run_id, plan,
                     policy, router, steps, save_interval, deadline_s,
                     world, flags) -> Tuple[int, Dict[int, float], dict]:
    """One incarnation of the miniature training loop (module
    docstring); returns (exit_code, losses, info)."""
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import monitor, resilience
    from apex_tpu.monitor import goodput
    from apex_tpu.resilience.health import IncidentResponder
    from apex_tpu.resilience.replay.journal import (
        FlightRecorder, batch_crc, journal_path,
    )
    from apex_tpu.resilience.remediation.canary import GPTCanary
    from apex_tpu.resilience.remediation.controller import (
        ControllerSink, RemediationController,
    )
    from apex_tpu.utils import AutoResume

    n_active = int(np.prod(training.mesh.devices.shape))
    goodput.run_header(router, run_id, devices=n_active)
    init_span = goodput.begin_span("init")
    recorder = FlightRecorder(journal_path(workdir), router=router)
    ar = AutoResume(workdir, interval=save_interval, mesh=training.mesh,
                    journal=recorder)
    mgr = resilience.ResilienceManager(
        buffer=resilience.RollbackBuffer(capacity=2, interval=3),
        policy=resilience.EscalationPolicy(max_rollbacks=2),
        router=router,
    )
    state = training.init_state()
    step0, state = ar.restore(state)
    recorder.header(
        run_id, "gpt", config=cfg.to_json(),
        corpus={"prefix": prefix}, devices=n_active, steps=steps, **flags,
    )
    recorder.anchor(step0, init=(step0 == 0))
    canary = GPTCanary(journal_path(workdir), workdir, training=training,
                       lm=lm, floor_step=step0)
    controller = RemediationController(
        policy=policy, router=router, save_dir=workdir,
        world_devices=world, canary_fn=canary, run_id=run_id,
    )
    router.add_sink(ControllerSink(controller))
    controller.adopt_pending(step0)
    window = monitor.MemorySink(max_records=256)
    router.add_sink(window)
    arm_responder = bool(plan.slow_steps or plan.hang_steps)
    responder = (IncidentResponder(
        deadline_s, router=router, window=window, autoresume=ar,
        dump_after=1.5,
    ) if arm_responder else None)
    bag = training.init_bag()
    mgr.buffer.snapshot(step0, state)
    init_span.close()
    losses: Dict[int, float] = {}
    rc: Optional[int] = None
    steps_run = 0
    step = step0
    slack = policy.probation_steps + save_interval + 2
    gb = cfg.global_batch
    try:
        while step < steps or (controller.in_probation
                               and step < steps + slack):
            ids = list(range(step * gb, (step + 1) * gb))
            x, y = lm.batch(ids)
            crc = batch_crc(x, y)
            xm, ym = training.reshape_batch(x, y)
            nan_armed = plan.take_nan(step)
            lr_scale = mgr.lr_scale
            with goodput.span("compile" if steps_run == 0 else "step",
                              step=step):
                out = training.train_step(
                    *state, bag, jnp.asarray(xm), jnp.asarray(ym),
                    jnp.asarray(nan_armed, jnp.float32),
                    jnp.asarray(lr_scale, jnp.float32),
                )
                (*state_l, bag, loss, verdict, layer_rms) = out
                state = tuple(state_l)
                if responder is not None and steps_run == 0:
                    responder.start()
                plan.maybe_slow(step)
                hang_fired = plan.maybe_hang(step)
            steps_run += 1
            if responder is not None:
                responder.beat(step)
            verdict_code = int(np.asarray(verdict))
            loss_f = float(np.asarray(loss))
            losses[step] = loss_f
            recorder.step(
                step, batch=[ids[0], ids[-1] + 1], batch_crc=crc,
                inject_nan=nan_armed, lr_scale=lr_scale, loss=loss_f,
                verdict=verdict_code, layer_rms=np.asarray(layer_rms),
            )
            params, flip_info = plan.maybe_bitflip(step, state[0])
            if flip_info is not None:
                state = (params,) + state[1:]
                recorder.event(step, "bitflip_injected", **flip_info)
            if hang_fired:
                # the bounded in-process stand-in for the responder's
                # os._exit(43): its forensic dump fired DURING the wedge
                # (watchdog thread); end the incarnation the way the
                # kill would — pending save tombstoned, sidecar flushed
                ar.prepare_incident_exit()
                recorder.flush()
                rc = int(ExitCode.INCIDENT)
                break
            action = mgr.resolve(step, verdict_code, loss=loss_f)
            if action == "halt":
                rc = int(ExitCode.FAILURE)
                break
            if action == "rollback":
                rolled_from = step
                step, rolled = mgr.do_rollback()
                state = rolled
                recorder.event(rolled_from, "rollback", to_step=step)
                continue
            if action != "skip":
                mgr.observe_good(step + 1, state)
            if verdict_code == 0:
                controller.on_clean_step(step)
            plan.maybe_sigterm(step)
            if ar.step(step + 1, state):
                decision = controller.on_preemption(step)
                recorder.flush()
                rc = decision.exit_code
                break
            anchor_due = bool(save_interval
                              and (step + 1) % save_interval == 0)
            # stand the dog down around the controller's own work (the
            # responder-stop idiom of the halt/termination saves): a
            # canary replay is minutes of legitimate host time on a slow
            # box, and a watchdog that flags its own remediation layer
            # as a stall would feed the controller a spurious case
            fence = responder is not None and (anchor_due
                                               or controller.has_pending)
            if fence:
                responder.stop()
            if anchor_due:
                # the canary can only audit COMMITTED anchors: force the
                # async manifest commit before the audit so the newest
                # segment is verifiable now, not at the next anchor —
                # at run end there is no next anchor, and a latent
                # corruption would complete the run undetected
                ar.finalize()
                controller.on_anchor(step + 1)
            decision = controller.process(step)
            if decision is not None:
                ar.finalize()
                recorder.flush()
                rc = decision.exit_code
                break
            if fence:
                responder.start()
            step += 1
    finally:
        if responder is not None:
            responder.stop()
    with goodput.span("shutdown", step=step):
        if rc is None:
            rc = int(ExitCode.OK)
            controller.run_end(max(step - 1, step0))
        ar.close()
        recorder.close()
    return rc, losses, {"step0": step0, "steps_run": steps_run,
                        "devices": n_active}


def run_sequence(
    faults: Sequence[FaultEvent],
    workdir: str,
    cache: TrainingCache,
    lm,
    prefix: str,
    policy: Optional[RemediationPolicy] = None,
    steps: int = 8,
    save_interval: int = 2,
    world: int = 8,
    slow_s: float = 5.0,
    deadline_s: float = 2.5,
    max_incarnations: int = 8,
    run_id: Optional[str] = None,
) -> SequenceResult:
    """Execute one fault sequence end to end (module docstring)."""
    from apex_tpu import monitor
    from apex_tpu.monitor import goodput
    from apex_tpu.resilience import chaos
    from apex_tpu.resilience.replay.replayer import determinism_guard

    os.makedirs(workdir, exist_ok=True)
    policy = policy if policy is not None else RemediationPolicy(
        probation_steps=3, clean_steps_to_close=2, max_restarts=6,
    )
    plan = chaos.FaultPlan(
        nan_steps={e.step for e in faults if e.kind == "nan"},
        slow_steps={e.step for e in faults if e.kind == "slow"},
        hang_steps={e.step for e in faults if e.kind == "hang"},
        bitflip_steps={e.step for e in faults if e.kind == "bitflip"},
        sigterm_steps={e.step for e in faults if e.kind == "sigterm"},
        slow_s=slow_s,
        hang_timeout_s=deadline_s * 4,
    )
    run_id = run_id or goodput.derive_run_id(workdir)
    flags = determinism_guard(pin=False)
    mem = monitor.MemorySink()
    incarnations: List[dict] = []
    losses: Dict[int, float] = {}
    outcome = "exhausted"
    prev_router = goodput.get_router()
    try:
        for index in range(max_incarnations):
            seq_state = RemediationState.load(workdir)
            n = seq_state.device_count(world)
            cfg, training = cache.get(n)
            router = monitor.MetricRouter([mem])
            goodput.set_router(router)
            try:
                rc, inc_losses, info = _run_incarnation(
                    training, cfg, lm, prefix, workdir, run_id, plan,
                    policy, router, steps, save_interval, deadline_s,
                    world, flags,
                )
            finally:
                goodput.set_router(None)
                router.close()
            losses.update(inc_losses)
            incarnations.append({
                "index": index, "exit_code": rc, "devices": n, **info,
            })
            logger.warning(
                "campaign sequence incarnation %d: %d device(s) exit %d "
                "(steps %s..+%s)", index, n, rc, info["step0"],
                info["steps_run"],
            )
            if rc == int(ExitCode.OK):
                outcome = "completed"
                break
            if rc == int(ExitCode.REMEDIATION_HALT):
                outcome = "halted"
                break
            if rc not in RESTARTABLE_EXIT_CODES:
                outcome = f"failed rc={rc}"
                break
            if rc == int(ExitCode.INCIDENT):
                # the supervisor contract: write the adoption note for
                # the next incarnation's controller
                seq_state = RemediationState.load(workdir)
                seq_state.pending = {"kind": "incident", "exit_code": rc,
                                     "incarnation": index}
                seq_state.save()
    finally:
        goodput.set_router(prev_router)
    records = mem.snapshot()
    return SequenceResult(
        faults=list(faults), run_id=run_id, outcome=outcome,
        incarnations=incarnations, records=records,
        remediation=[r for r in records if r.get("kind") == "remediation"],
        losses=losses,
    )


# -- invariants --------------------------------------------------------------


def _match_faults(faults: Sequence[FaultEvent],
                  terminals: Sequence[dict]) -> bool:
    """Exact bipartite match: every fault accounted by exactly one
    terminal record, every terminal accounted by exactly one fault
    (backtracking; fault sets are tiny)."""
    if len(faults) != len(terminals):
        return False

    def ok(fault: FaultEvent, term: dict) -> bool:
        return ((term.get("finding"), term.get("verdict"))
                in FAULT_TERMINALS[fault.kind])

    def solve(i: int, used: frozenset) -> bool:
        if i == len(faults):
            return True
        for j, term in enumerate(terminals):
            if j not in used and ok(faults[i], term):
                if solve(i + 1, used | {j}):
                    return True
        return False

    return solve(0, frozenset())


def _quarantine_verified(result: SequenceResult, case_id: str) -> bool:
    """True when the case's quarantine rests on VERIFIED evidence: a
    canary-confirmed verify record, or an ``sdc`` finding whose
    detection evidence IS a canary/bisector re-execution."""
    case_records = [r for r in result.remediation
                    if r.get("case") == case_id]
    if any(r.get("action") == "verify" and r.get("verdict") == "confirmed"
           for r in case_records):
        return True
    if case_records and case_records[0].get("finding") == "sdc":
        for r in case_records:
            for ev in r.get("evidence") or []:
                if isinstance(ev, dict) and (
                        ev.get("kind") == "canary" or ev.get("found")):
                    return True
    return False


def check_invariants(
    result: SequenceResult,
    reference_losses: Optional[Dict[int, float]] = None,
    final_step: Optional[int] = None,
    loss_tol: float = 5e-2,
) -> List[str]:
    """The campaign's pass/fail judgment (module docstring); returns
    the violations (empty = the sequence healed correctly)."""
    from apex_tpu.monitor.goodput.accountant import BADPUT_PHASES, account

    violations: List[str] = []
    if result.outcome != "completed":
        violations.append(f"sequence did not complete: {result.outcome}")

    # 1. goodput partition identity, digit for digit, across EVERY
    # incarnation of the run id
    rep = account(result.records, run_id=result.run_id)
    fields = rep.fields()
    total = fields["productive_s"]
    for phase in BADPUT_PHASES:
        total = total + fields[f"badput_{phase}_s"]
    total = total + fields["unattributed_s"]
    if total != fields["wall_s"]:
        violations.append(
            f"goodput partition identity broken: re-added {total!r} != "
            f"wall {fields['wall_s']!r}"
        )
    n_headers = len([
        r for r in result.records
        if r.get("kind") == "run" and r.get("run_id") == result.run_id
    ])
    if rep.incarnations != n_headers:
        violations.append(
            f"accountant saw {rep.incarnations} incarnation(s), stream "
            f"has {n_headers} run header(s)"
        )

    # 2. one terminal verdict per fault, exactly
    terminals = result.terminals
    if not _match_faults(result.faults, terminals):
        violations.append(
            f"fault/terminal mismatch: faults="
            f"{[(f.kind, f.step) for f in result.faults]} terminals="
            f"{[(t.get('finding'), t.get('verdict')) for t in terminals]}"
        )

    # 3. no quarantine without verified evidence (the false-positive
    # pin: the broken verify_before_quarantine=False policy trips this)
    for rec in result.remediation:
        if rec.get("action") != "quarantine":
            continue
        if not _quarantine_verified(result, rec.get("case")):
            violations.append(
                f"case {rec.get('case')} quarantined WITHOUT canary "
                f"verification (finding={rec.get('finding')}) — the "
                f"policy table is broken"
            )

    # 4. post-recovery loss trajectory pins to the uninterrupted
    # reference
    if reference_losses is not None:
        step = (final_step if final_step is not None
                else max(reference_losses))
        got = result.losses.get(step)
        want = reference_losses.get(step)
        if got is None:
            violations.append(f"no loss recorded at final step {step}")
        elif want is not None and abs(got - want) > loss_tol:
            violations.append(
                f"final loss diverged from the uninterrupted reference: "
                f"|{got:.4f} - {want:.4f}| > {loss_tol}"
            )
    return violations


def minimize_failing(
    faults: Sequence[FaultEvent],
    run_and_check: Callable[[Sequence[FaultEvent]], List[str]],
) -> Tuple[List[FaultEvent], List[str]]:
    """Drop-one-fault ddmin: shrink a failing sequence to a 1-minimal
    reproducer. ``run_and_check`` re-runs a candidate (fresh workdir!)
    and returns its violations; deterministic because every re-run is
    seeded by the same fault list."""
    current = list(faults)
    violations = run_and_check(current)
    if not violations:
        return current, []
    changed = True
    while changed and len(current) > 1:
        changed = False
        for i in range(len(current)):
            candidate = current[:i] + current[i + 1:]
            cand_violations = run_and_check(candidate)
            if cand_violations:
                current, violations = candidate, cand_violations
                changed = True
                break
    return current, violations


def run_campaign(
    workroot: str,
    n_sequences: int = 20,
    seed: int = 0,
    steps: int = 8,
    policy: Optional[RemediationPolicy] = None,
    minimize: bool = False,
    cache: Optional[TrainingCache] = None,
) -> dict:
    """Run ``n_sequences`` seeded sequences + the clean reference;
    returns ``{"passed", "failed", "sequences": [...]}`` where each
    entry carries the faults, outcome, violations, and (when
    ``minimize`` and failing) the minimized reproducer."""
    from apex_tpu.data import IndexedTokenDataset, LMDataset
    from apex_tpu.resilience.replay.targets import synthetic_corpus

    cfg = campaign_config()
    cache = cache if cache is not None else TrainingCache(cfg)
    prefix = synthetic_corpus(cfg.vocab, n_tokens=20_000)
    lm = LMDataset(IndexedTokenDataset(prefix), seq_len=cfg.seq_len)

    # the uninterrupted reference: same machinery, zero faults — its
    # losses are what every healed sequence must pin to, and its zero
    # remediation cases prove the audit-clean path costs no verdicts
    reference = run_sequence(
        [], os.path.join(workroot, "reference"), cache, lm, prefix,
        policy=policy, steps=steps,
    )
    entries: List[dict] = []
    failed = 0
    for i in range(n_sequences):
        faults = random_sequence(seed + i, steps=steps)
        workdir = os.path.join(workroot, f"seq-{i:03d}")
        result = run_sequence(faults, workdir, cache, lm, prefix,
                              policy=policy, steps=steps)
        violations = check_invariants(
            result, reference_losses=reference.losses,
            final_step=steps - 1,
        )
        entry = {
            "seed": seed + i,
            "faults": [(f.kind, f.step) for f in faults],
            "outcome": result.outcome,
            "incarnations": len(result.incarnations),
            "terminals": [(t.get("finding"), t.get("verdict"))
                          for t in result.terminals],
            "violations": violations,
        }
        if violations:
            failed += 1
            if minimize:
                attempt = [0]

                def rerun(candidate, _i=i, _attempt=attempt):
                    # a FRESH workdir per candidate (minimize_failing's
                    # contract): same-length candidates must not inherit
                    # the previous candidate's checkpoints/state
                    _attempt[0] += 1
                    d = os.path.join(workroot, f"seq-{_i:03d}-min-"
                                     f"{_attempt[0]:02d}")
                    r = run_sequence(candidate, d, cache, lm, prefix,
                                     policy=policy, steps=steps)
                    return check_invariants(
                        r, reference_losses=reference.losses,
                        final_step=steps - 1,
                    )

                minimal, min_violations = minimize_failing(faults, rerun)
                entry["minimal"] = [(f.kind, f.step) for f in minimal]
                entry["minimal_violations"] = min_violations
        entries.append(entry)
        logger.warning(
            "campaign %d/%d: faults=%s -> %s%s", i + 1, n_sequences,
            entry["faults"], result.outcome,
            f" VIOLATIONS={violations}" if violations else " ok",
        )
    return {
        "passed": n_sequences - failed,
        "failed": failed,
        "reference_losses": reference.losses,
        "sequences": entries,
    }
