"""In-memory rollback: host-side snapshot ring + escalation policy.

A checkpoint restore costs a full deserialization and loses every step
since the last save interval; most anomalies (one poisoned batch, a
transient loss spike that slipped a bad update in) only need to rewind a
few steps. ``RollbackBuffer`` keeps the last K known-good states ON HOST
(numpy copies — HBM holds one live state, the ring lives in host RAM,
which is plentiful next to HBM) and restores them with their original
shardings in milliseconds.

``ResilienceManager`` is the host half of the sentinel loop: it maps the
in-graph verdict (resilience.sentinel) to an action under a bounded
``EscalationPolicy`` —

    skip batch  ->  rollback + LR dampen  ->  halt-and-checkpoint

- retries are bounded (``max_rollbacks`` per run);
- repeated rollback to the SAME snapshot backs off to the next-older
  one (the newest "good" state evidently wasn't);
- each rollback dampens the LR (multiply ``lr_scale`` into the update
  inside the step) so the run re-approaches the cliff more slowly;
- every anomaly is appended to a per-run jsonl anomaly log AND emitted
  through the shared telemetry schema (``apex_tpu.monitor.make_record``):
  pass ``router=`` a :class:`~apex_tpu.monitor.MetricRouter` and the
  anomaly stream lands in the same sinks as the metric stream, joinable
  on ``step`` (one record shape for anomalies and metrics).

The data stream rewinds with the state: ``rollback()`` returns the step
to resume FROM, and the caller rebuilds its sampler/iterator at that
step (the Megatron samplers' ``consumed_samples`` resume mechanism, see
examples/gpt/pretrain_gpt.py).
"""

import collections
import dataclasses
import json
import logging
import os
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from apex_tpu.resilience.sentinel import (
    VERDICT_HALT,
    VERDICT_OK,
    VERDICT_ROLLBACK,
    VERDICT_SKIP,
    verdict_name,
)

logger = logging.getLogger("apex_tpu.resilience")


class RollbackBuffer:
    """Ring of the last ``capacity`` good state snapshots.

    ``snapshot`` copies every leaf to host (``np.array`` — a real copy,
    so later donation/mutation of the live buffers cannot reach it) and
    records each jax.Array leaf's sharding; ``rollback`` device_puts the
    copy back with the same shardings.
    """

    def __init__(self, capacity: int = 2, interval: int = 1):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if interval < 1:
            raise ValueError(f"interval must be >= 1, got {interval}")
        self.capacity = int(capacity)
        self.interval = int(interval)
        self._ring = collections.deque(maxlen=self.capacity)

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def steps(self) -> List[int]:
        return [s for s, _, _ in self._ring]

    def snapshot(self, step: int, state: Any) -> None:
        import jax

        from apex_tpu.monitor.goodput.spans import span as _goodput_span

        # the device-to-host copy blocks the loop (6.3 s at 345M on a
        # v5e): booked in the run's ledger and, in a capture, on the
        # profiler's clock
        with _goodput_span("snapshot", step=int(step)):
            host = jax.tree_util.tree_map(lambda x: np.array(x), state)
        shardings = jax.tree_util.tree_map(
            lambda x: x.sharding if isinstance(x, jax.Array) else None, state
        )
        self._ring.append((int(step), host, shardings))

    def maybe_snapshot(self, step: int, state: Any) -> bool:
        """Snapshot on the configured cadence; True when one was taken."""
        if step % self.interval == 0:
            self.snapshot(step, state)
            return True
        return False

    def rollback(self, pop: bool = False) -> Tuple[int, Any]:
        """(step, state) of the newest snapshot; ``pop=True`` discards it
        first and returns the next-older one (escalation after a rollback
        that failed to clear the anomaly)."""
        if pop and len(self._ring) > 1:
            self._ring.pop()
        if not self._ring:
            raise RuntimeError("rollback requested but no snapshots held")
        import jax

        step, host, shardings = self._ring[-1]
        state = jax.tree_util.tree_map(
            lambda h, s: h if s is None else jax.device_put(h, s),
            host, shardings,
        )
        return step, state

    def clear(self) -> None:
        self._ring.clear()


@dataclasses.dataclass
class EscalationPolicy:
    """Bounds on the skip -> rollback -> halt ladder (host side).

    The IN-GRAPH escalation (how many consecutive anomalies before the
    verdict itself says ROLLBACK/HALT) lives in AnomalySentinel's
    budgets; this bounds what the host will actually do across a run.
    """

    max_rollbacks: int = 3          # per run; beyond this, halt
    lr_dampen: float = 0.5          # lr_scale multiplier per rollback
    min_lr_scale: float = 1.0 / 16  # dampening floor
    # a rollback that lands on the same snapshot as the previous one
    # pops to the next-older snapshot (backoff through history)
    backoff_on_repeat: bool = True


class ResilienceManager:
    """Host-side driver: verdicts in, actions out, anomaly log to disk.

    Usage (see examples/gpt/pretrain_gpt.py for the full wiring)::

        mgr = ResilienceManager(buffer=RollbackBuffer(2, interval=10),
                                policy=EscalationPolicy(),
                                log_path=os.path.join(save_dir, "anomalies.jsonl"))
        while step < total:
            ..., verdict = train_step(..., lr_scale=mgr.lr_scale)
            action = mgr.resolve(step, int(verdict), loss=float(loss))
            if action == "halt":
                save_checkpoint_verified(...); break
            if action == "rollback":
                step, state = mgr.do_rollback()
                it = make_iterator(step)      # re-wind the data stream
                continue
            mgr.observe_good(step + 1, state) # feeds the snapshot ring
            step += 1
    """

    def __init__(
        self,
        buffer: Optional[RollbackBuffer] = None,
        policy: Optional[EscalationPolicy] = None,
        log_path: Optional[str] = None,
        on_event: Optional[Callable[[dict], None]] = None,
        router=None,
    ):
        self.buffer = buffer
        self.policy = policy or EscalationPolicy()
        self.log_path = log_path
        self.on_event = on_event
        self.router = router
        self.lr_scale = 1.0
        self.rollbacks_used = 0
        self.events: List[dict] = []
        self._last_restore_step: Optional[int] = None
        if log_path:
            os.makedirs(os.path.dirname(os.path.abspath(log_path)), exist_ok=True)

    # -- anomaly log -------------------------------------------------------

    def _record(self, step: int, kind: str, **fields) -> dict:
        # the monitor schema IS the historical anomaly-log line shape
        # ({"t", "step", "kind", ...}), so routing through it keeps every
        # existing anomalies.jsonl consumer working byte-for-byte
        from apex_tpu.monitor.router import make_record

        event = make_record(kind, step, **fields)
        self.events.append(event)
        if self.log_path:
            try:
                with open(self.log_path, "a") as f:
                    f.write(json.dumps(event) + "\n")
            except OSError as e:  # pragma: no cover - log loss is non-fatal
                logger.warning("anomaly log write failed: %s", e)
        if self.router is not None:
            self.router.emit(event)
        if self.on_event:
            self.on_event(event)
        return event

    # -- verdict -> action -------------------------------------------------

    def resolve(self, step: int, verdict: int, loss: Optional[float] = None) -> str:
        """Map a step's verdict to 'ok' | 'skip' | 'rollback' | 'halt'.

        ROLLBACK degrades to 'halt' when retries are exhausted or no
        snapshot exists (nothing to restore is not a recoverable state).
        """
        verdict = int(verdict)
        if verdict == VERDICT_OK:
            return "ok"
        if verdict == VERDICT_SKIP:
            self._record(step, "skip", loss=loss, lr_scale=self.lr_scale)
            logger.warning("anomalous step %d: skipped (loss=%s)", step, loss)
            return "skip"
        if verdict == VERDICT_ROLLBACK:
            if self.buffer is None or len(self.buffer) == 0:
                logger.error("rollback verdict at step %d but no snapshots; halting", step)
                self._record(step, "halt", loss=loss, reason="no snapshots")
                return "halt"
            if self.rollbacks_used >= self.policy.max_rollbacks:
                logger.error(
                    "rollback budget exhausted (%d) at step %d; halting",
                    self.policy.max_rollbacks, step,
                )
                self._record(step, "halt", loss=loss,
                             reason="rollback budget exhausted")
                return "halt"
            self._record(step, "rollback", loss=loss, lr_scale=self.lr_scale)
            return "rollback"
        self._record(step, "halt", loss=loss, reason="sentinel verdict")
        return "halt"

    def do_rollback(self) -> Tuple[int, Any]:
        """Restore the snapshot chosen by the policy; dampens LR.

        Returns ``(step, state)`` — resume the loop AT ``step`` with the
        data iterator rebuilt for it.
        """
        assert self.buffer is not None
        pop = (
            self.policy.backoff_on_repeat
            and self._last_restore_step is not None
            and self.buffer.steps
            and self.buffer.steps[-1] == self._last_restore_step
        )
        # goodput span: recovery wall time (snapshot restore +
        # device_put) is rollback badput in the run-level ledger; emitted
        # through THIS manager's router so the span lands in the same
        # stream as the rollback/rollback_restore events below
        from apex_tpu.monitor.goodput.spans import span as _goodput_span

        with _goodput_span("rollback", router=self.router):
            step, state = self.buffer.rollback(pop=bool(pop))
        self.rollbacks_used += 1
        self.lr_scale = max(
            self.policy.min_lr_scale, self.lr_scale * self.policy.lr_dampen
        )
        self._last_restore_step = step
        self._record(
            step, "rollback_restore",
            lr_scale=self.lr_scale, rollbacks_used=self.rollbacks_used,
            popped=bool(pop),
        )
        logger.warning(
            "rolled back to step %d (rollback %d/%d, lr_scale=%.4f)",
            step, self.rollbacks_used, self.policy.max_rollbacks, self.lr_scale,
        )
        return step, state

    def observe_good(self, step: int, state: Any) -> None:
        """Feed a post-step known-good state to the snapshot ring."""
        if self.buffer is not None:
            self.buffer.maybe_snapshot(step, state)
