"""Stall watchdog and on-anomaly profiler capture.

The resilience subsystem reacts to signals the system DELIVERS — SIGTERM
before preemption, NaN verdicts from the sentinel. A wedged collective, a
deadlocked host thread, or a hung device delivers nothing: the step simply
never finishes. :class:`StallWatchdog` is the complement — a daemon
heartbeat thread that flags a step exceeding its deadline from OUTSIDE
the (possibly stuck) training thread. Its ``escalations`` ladder carries
the incident-response runtime (``apex_tpu.resilience.health``): warn at
the deadline, then arbitrary once-per-episode callbacks at higher
multiples (forensic dump, coordinated self-termination).

:class:`ProfilerTrigger` closes the observability loop: when the sentinel
escalates (or at a step requested up front with ``--profile-step``), it
snapshots a ``jax.profiler.trace`` window around the next steps, so the
capture of a pathological step exists BEFORE anyone knew to ask for it.
Captures go to timestamped subdirs; ``annotate``/``step_annotation``
spans (utils/timers.py) and ``jax.named_scope`` names (pipeline
schedules) appear inside them.
"""

import logging
import os
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

logger = logging.getLogger("apex_tpu.monitor")


class StallWatchdog:
    """Fire ``on_stall`` when no heartbeat lands within ``deadline_s``.

    The training loop calls :meth:`beat` once per step; a daemon thread
    polls the wall clock. On expiry, ``on_stall(info)`` runs ONCE in the
    watchdog thread (info: last step, seconds since its beat) and the dog
    re-arms on the next beat — a recovered stall can fire again, a dead
    loop does not spam. The default action logs; pass ``router=`` a
    :class:`~apex_tpu.monitor.MetricRouter` and each stall ALSO lands in
    the record stream as a ``kind="stall"`` event plus a ``kind="span"``
    record (phase ``stall``, spanning from the last heartbeat) — the
    stream the goodput accountant reads, so detected dead time shows up
    as badput instead of living only in this object's memory and the
    warning log. ``on_stall`` (e.g. a :class:`ProfilerTrigger`) composes
    with the router.

    Escalation ladder: ``escalations`` is an ordered sequence of
    ``(multiplier, callback)`` pairs. When the overdue time exceeds
    ``multiplier * deadline_s`` the callback fires ONCE per stall
    episode, in the watchdog thread, with the same ``info`` dict as
    ``on_stall`` (plus ``beat_mono``, the monotonic timestamp of the
    last heartbeat, so an escalation can anchor a span at the start of
    the dead time). A beat re-arms every level. This is the deadline
    machinery :class:`~apex_tpu.resilience.health.IncidentResponder`
    builds the warn → dump → terminate ladder on; a callback that raises
    is logged and does not stop later levels — the dog must outlive its
    handlers.

    Usable as a context manager; ``beat`` and ``stop`` are thread-safe.
    """

    def __init__(
        self,
        deadline_s: float,
        on_stall: Optional[Callable[[dict], None]] = None,
        poll_s: Optional[float] = None,
        router=None,
        escalations: Sequence[Tuple[float, Callable[[dict], None]]] = (),
    ):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        self.poll_s = float(poll_s) if poll_s else min(1.0, self.deadline_s / 4)
        self.on_stall = on_stall
        self.router = router
        # key= so equal multipliers never fall through to comparing the
        # (unorderable) callbacks; ties keep registration order
        self.escalations: List[Tuple[float, Callable[[dict], None]]] = sorted(
            ((float(mult), cb) for mult, cb in escalations),
            key=lambda pair: pair[0],
        )
        for mult, _ in self.escalations:
            if mult < 1.0:
                raise ValueError(
                    f"escalation multipliers are in units of deadline_s and "
                    f"must be >= 1.0 (the base warn), got {mult}"
                )
        self.stalls: List[dict] = []
        self._lock = threading.Lock()
        self._last_beat = time.monotonic()
        self._last_step: Optional[int] = None
        self._fired = False
        self._fired_levels: set = set()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "StallWatchdog":
        if self._thread is not None:
            raise RuntimeError("watchdog already started")
        self._stop.clear()  # restartable after stop() (pause/resume)
        self._last_beat = time.monotonic()
        self._thread = threading.Thread(
            target=self._run, name="apex-tpu-stall-watchdog", daemon=True
        )
        self._thread.start()
        return self

    def beat(self, step: Optional[int] = None) -> None:
        """Mark the training loop alive (call once per completed step)."""
        with self._lock:
            self._last_beat = time.monotonic()
            if step is not None:
                self._last_step = int(step)
            self._fired = False
            self._fired_levels.clear()

    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            fire: List[Optional[Callable[[dict], None]]] = []
            with self._lock:
                overdue = time.monotonic() - self._last_beat
                beat_mono = self._last_beat
                step = self._last_step
                if overdue <= self.deadline_s:
                    continue
                if not self._fired:
                    self._fired = True
                    fire.append(None)  # the base warn level
                for i, (mult, cb) in enumerate(self.escalations):
                    if (overdue > mult * self.deadline_s
                            and i not in self._fired_levels):
                        self._fired_levels.add(i)
                        fire.append(cb)
            if not fire:
                continue
            info = {
                "step": step,
                "overdue_s": overdue,
                "deadline_s": self.deadline_s,
                "beat_mono": beat_mono,
            }
            # each poll's newly-due actions run on their OWN daemon
            # thread, NOT the poll loop: a handler blocked forever — the
            # classic case being router.event stuck on the router lock
            # under a hung sink, the very hung-IO fault the ladder
            # exists to bound — must not stall the loop, or later levels
            # (the terminate stage's os._exit) would never fire. Within
            # one poll the actions run sequentially, preserving ladder
            # order; levels due at different polls get fresh threads.
            threading.Thread(
                target=self._fire, args=(fire, info),
                name="apex-tpu-watchdog-fire", daemon=True,
            ).start()

    def _fire(self, fire: List[Optional[Callable[[dict], None]]],
              info: dict) -> None:
        for cb in fire:
            # staleness gate, re-checked immediately before EACH action:
            # between the poll snapshot and this thread running, the
            # episode may have ended — a fresh beat (the step completed
            # after all) or stop() (the loop stood the dog down before a
            # deliberate blocking save). A stale terminate would
            # os._exit a job that already recovered, tombstoning the
            # very save in progress; skipping is always safe because a
            # still-dead loop re-blows the deadline and re-fires.
            with self._lock:
                if (self._stop.is_set()
                        or self._last_beat != info["beat_mono"]):
                    return
            if cb is None:
                self._warn(dict(info))
            else:
                try:
                    cb(dict(info))
                except Exception as e:  # outlive the escalation too
                    logger.warning("watchdog escalation failed: %s", e)

    def _warn(self, info: dict) -> None:
        """The base (1x deadline) level: log + stall record + stall span."""
        step, overdue = info["step"], info["overdue_s"]
        self.stalls.append(info)
        logger.warning(
            "stall: no step heartbeat for %.1fs (deadline %.1fs, "
            "last step %s)", overdue, self.deadline_s, step,
        )
        if self.router is not None:
            try:
                self.router.event(
                    "stall", -1 if step is None else step,
                    overdue_s=overdue, deadline_s=self.deadline_s,
                )
                # the stall's duration as a goodput span: measured
                # FROM the last heartbeat — the dead time started
                # when the loop went quiet, not when the dog barked
                from apex_tpu.monitor.goodput.spans import emit_span

                emit_span(
                    self.router, "stall", info["beat_mono"], overdue,
                    step=step,
                )
            except Exception as e:  # the dog must outlive its sinks
                logger.warning("stall record emit failed: %s", e)
        if self.on_stall is not None:
            try:
                self.on_stall(info)
            except Exception as e:  # the dog must outlive its handler
                logger.warning("on_stall handler failed: %s", e)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5 * self.poll_s)
            self._thread = None

    def __enter__(self) -> "StallWatchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


class ProfilerTrigger:
    """Capture a ``jax.profiler`` trace window on demand.

    Drive it from the step loop::

        trigger = ProfilerTrigger(log_dir, window_steps=2)
        trigger.request(step=args.profile_step)      # up-front request
        while ...:
            trigger.maybe_start(step)                # BEFORE the step
            ... run step, read verdict ...
            trigger.on_verdict(step, int(verdict))   # anomaly capture
            trigger.maybe_stop(step)                 # AFTER block_until_ready

    ``on_verdict`` arms a capture of the NEXT ``window_steps`` steps when
    the sentinel says ROLLBACK or worse — the steps that re-run the
    region that just blew up. One capture at a time; each lands in
    ``<log_dir>/<tag>-step<NNN>`` and is appended to ``captures``.
    Profiler failures are logged, never raised: losing a trace must not
    lose the run. Remember the benchmarking caveat: callers must
    ``jax.block_until_ready`` the step's outputs before ``maybe_stop`` or
    in-flight device work leaks out of the window.

    Pass ``router=`` a :class:`~apex_tpu.monitor.MetricRouter` and each
    completed capture emits its own ``kind="profile"`` record
    (path/reason/end_step at the capture's start step) — the wiring the
    examples previously hand-rolled as an ``on_capture`` lambda.
    """

    def __init__(
        self,
        log_dir: str,
        window_steps: int = 2,
        on_capture: Optional[Callable[[dict], None]] = None,
        router=None,
    ):
        if window_steps < 1:
            raise ValueError(f"window_steps must be >= 1, got {window_steps}")
        self.log_dir = log_dir
        self.window_steps = int(window_steps)
        self.on_capture = on_capture
        self.router = router
        self.captures: List[dict] = []
        # guards the _requested/_active handshake: request() is called
        # from the watchdog thread (capture_incident arms the trigger in
        # the escalation path) while maybe_start/maybe_stop run on the
        # step loop — check-then-act on these two fields must be atomic.
        # Profiler I/O never runs under this lock (claim inside, I/O
        # outside), so a slow trace start cannot stall the watchdog.
        self._state_lock = threading.Lock()
        self._requested: Optional[dict] = None  # {"step": int|None, "reason"}
        self._active: Optional[dict] = None

    # -- arming ------------------------------------------------------------

    def request(self, step: Optional[int] = None, reason: str = "requested") -> None:
        """Arm a capture: at ``step`` (None/past-due = the next step).

        An immediate request (``step=None`` — the anomaly path) REPLACES
        a pending scheduled one: the blowup happening now outranks a
        --profile-step appointment for later. A capture already rolling
        is never preempted.
        """
        with self._state_lock:
            if self._active is not None:
                return
            pending = self._requested
            if pending is None or (step is None
                                   and pending["step"] is not None):
                self._requested = {"step": step, "reason": reason}

    def on_verdict(self, step: int, verdict: int) -> None:
        """Arm on sentinel escalation (>= VERDICT_ROLLBACK)."""
        from apex_tpu.resilience.sentinel import VERDICT_ROLLBACK

        if int(verdict) >= VERDICT_ROLLBACK:
            self.request(reason=f"verdict={int(verdict)}")

    # -- step-loop hooks ---------------------------------------------------

    def maybe_start(self, step: int) -> bool:
        """Start the trace if a request is due at ``step``; True if so."""
        import jax

        with self._state_lock:
            req = self._requested
            if req is None or self._active is not None:
                return False
            if req["step"] is not None and step < req["step"]:
                return False
            path = os.path.join(
                self.log_dir,
                f"{req['reason'].replace('=', '')}-step{step:06d}"
            )
            # claim under the lock so a concurrent request() sees the
            # capture as rolling; the profiler I/O runs outside it
            self._requested = None
            self._active = {
                "path": path, "start_step": step, "reason": req["reason"],
            }
        try:
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
        except Exception as e:  # pragma: no cover - backend-dependent
            logger.warning("profiler capture failed to start: %s", e)
            with self._state_lock:
                self._active = None
            return False
        logger.info("profiler capture started: %s", path)
        return True

    def maybe_stop(self, step: int) -> Optional[dict]:
        """Stop after ``window_steps`` steps; returns the capture info."""
        import jax

        with self._state_lock:
            act = self._active
            if act is None or \
                    step - act["start_step"] + 1 < self.window_steps:
                return None
            # claim: exactly one caller stops this capture
            self._active = None
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # pragma: no cover - backend-dependent
            logger.warning("profiler capture failed to stop: %s", e)
            return None
        info = {**act, "end_step": step}
        self.captures.append(info)
        if self.router is not None:
            try:
                self.router.event(
                    "profile", info["start_step"], path=info["path"],
                    reason=info["reason"], end_step=step,
                )
            except Exception as e:
                logger.warning("profile record emit failed: %s", e)
        if self.on_capture is not None:
            try:
                self.on_capture(info)
            except Exception as e:
                logger.warning("on_capture handler failed: %s", e)
        logger.info("profiler capture written: %s", act["path"])
        return info

    def close(self) -> None:
        """Abort any in-flight capture (end of run)."""
        with self._state_lock:
            act = self._active
            self._active = None
        if act is not None:
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception:  # pragma: no cover
                pass
