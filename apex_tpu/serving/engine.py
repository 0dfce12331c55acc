"""Overload-hardened serving core: continuous batching over the KV pool.

The scheduler that turns the library's decode path (``models/generate``
semantics over the transformer's cache variables) into a SERVER — and a
robustness-first one: a server that melts under load is worse than no
server, so every resource here is bounded and every overflow is SHED
with a booked reason, never buffered without limit
(docs/serving.md; ROADMAP item 1).

Continuous (in-flight) batching: the engine runs a tick loop. Each tick
admits up to ``max_prefills_per_tick`` queued requests (one compiled
prefill each, bucketed by prompt length), then advances EVERY in-flight
request by one token through ONE compiled decode step — requests join
and leave the batch at tick granularity, no waiting for stragglers to
finish a "batch".

One decode step stays in flight across ticks: tick t dispatches step t
and only THEN reads step t-1's tokens, appends them and releases the
requests they complete, so the device runs step t while the host books
step t-1, returns and takes the next submissions; the period of a tick
is max(host, device) and not their sum. Step t's token inputs are step
t-1's output where it lies on the device. That is exact because the
schedule never depends on a token's value: a request ends at
``max_new_tokens`` (the host knows, before step t-1 is read, which
lanes it completes, and leaves them out of step t), deadlines and
cancels are host events, and positions advance by one. A stop token
would change that: the one step dispatched after a stop would be
wasted and its token dropped. A request stays live until its last
token is read; a token whose request was cancelled, timed out or
extracted while its step was in flight is dropped (its lane and blocks
may already serve another request: the device orders that prefill
after the step in flight, both take the donated pool). ``idle`` is
false while a step is in flight; ``drain`` and ``extract`` read it
first. The decode step calls the model ONCE for a batch of
``lanes`` tokens over the KV pool where it lies (kvcache.py): per-lane
state (its position, block table and sampling temperature) rides in the
paged cache the attention layers are handed, each layer writes every
lane's new key and value into its one pool slot and attends by block
table and length (``ops.paged_decode_attention``), so per-request
positions diverge freely, nothing is gathered into a contiguous window,
and a lane costs what it holds (``stats()["decode_keys_read_share"]``).

The host's own account of every tick: its wall time booked into five
parts that add up to it exactly (``TICK_PARTS``: admission, each
prefill through its first token, the decode call's dispatch, the wait
for a device value of the decode path, and the bookkeeping that is the
rest), with the tick's start, the lanes stepped, the prefills run and
whether the step went ahead, kept in a ring of the newest
``TICK_LOG_TICKS`` ticks (:meth:`ServingEngine.tick_log`,
``stats()["host_tick_ms"]``). Each part also opens a
``jax.profiler.TraceAnnotation`` named ``engine.<part>`` while a
profiler session records (one flag test a tick otherwise), nested in
the goodput ``prefill`` / ``decode`` spans, so a capture puts the
device's idle gaps down to the part of the tick that kept it waiting.

Zero steady-state recompiles: prefill shapes are BUCKETED (block-size
multiples, doubling up to ``max_seq_len``) and every bucket plus the
decode step is AOT-compiled (``jit(...).lower(...).compile()``) in
:meth:`ServingEngine.start`, so steady traffic executes pre-compiled
artifacts only. A PR-3 :class:`~apex_tpu.monitor.CompileWatcher`
created AFTER the warmup ticks once per scheduler tick; any compile it
sees is a steady-state violation surfaced as
:attr:`ServingEngine.steady_state_compiles` (the selftest and the
overload drill assert it stays 0).

Robustness surface (the ops layer transferring wholesale):

- **bounded admission queue + load shedding** — ``submit`` refuses with
  a booked reason (``queue_full``, ``ttft_budget``, ``malformed``,
  ``too_long``, ``draining``) the moment a bound would be exceeded;
- **per-request deadlines** — enforced at EVERY tick, in queue and in
  batch: expired requests are evicted, their KV blocks reclaimed, and
  the ending booked ``timed_out`` — never a silent drop;
- **wedged-decode defense** — pass an
  :class:`~apex_tpu.resilience.health.IncidentResponder` (or a bare
  watchdog) as ``watchdog=``: the engine beats it once per tick, and
  ``bundle_extra=engine.inflight_table`` puts the in-flight request
  table into the forensic dump before the coordinated exit 43;
- **graceful drain** — :meth:`drain` stops admission, finishes or
  deadline-evicts the in-flight requests within the grace budget
  (PR-8's ``APEX_TPU_PREEMPTION_GRACE_S`` convention via
  ``utils.autoresume.TerminationNotice``), and emits terminal states
  for every request;
- **chaos drills** — a :class:`~apex_tpu.resilience.chaos.FaultPlan`
  injects slow-decode ticks and host-loop wedges inside the tick, and
  the load generator (loadgen.py) consumes its client-abandon /
  malformed-prompt / burst-arrival faults.

Telemetry: ``kind="request"`` lifecycle records (lifecycle.py) plus
goodput spans — ``prefill`` and ``decode`` are PRODUCTIVE phases, so
the PR-7 accountant's partition identity extends to request wall clock
digit-for-digit. Every lifecycle emission also feeds the engine's
:class:`~apex_tpu.serving.trace.emit.TraceEmitter` (the ``trace=``
hook on ``emit_request_record``), growing one causal ``kind="trace"``
span tree per request — queue wait, prefill, decode segments, drain
evictions and hang exposure all become spans the request x-ray
(``python -m apex_tpu.serving.trace``) can decompose.
"""

import collections
import dataclasses
import logging
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from apex_tpu.monitor.goodput.spans import open_annotation, profiling, span
from apex_tpu.serving.kvcache import BlockAllocator, CacheSpec, blocks_needed
from apex_tpu.serving.lifecycle import (
    ADMITTED,
    CANCELLED,
    COMPLETED,
    DECODE,
    FAILED,
    PREFILL,
    QUEUED,
    REJECTED,
    TIMED_OUT,
    Request,
    emit_request_record,
    transition,
)
from apex_tpu.serving.trace.emit import TraceEmitter

logger = logging.getLogger("apex_tpu.serving")

__all__ = ["ServingConfig", "ServingEngine", "TICK_PARTS", "TICK_LOG_TICKS"]

#: the parts of a tick's host time (module docstring), in the order a tick
#: runs them; ``book`` is the tick less the other four
TICK_PARTS = ("admit", "prefill", "dispatch", "wait", "book")
#: ticks the account keeps, the newest (a 50 s chat window runs 6-7,000)
TICK_LOG_TICKS = 65536
_TICK_RECORD = np.dtype(
    [("start_ns", np.int64)] + [(p + "_ns", np.int64) for p in TICK_PARTS]
    + [("lanes", np.int32), ("prefills", np.int32), ("ahead", np.bool_)])
_ADMIT, _PREFILL, _DISPATCH, _WAIT, _BOOK = range(len(TICK_PARTS))


def _ema(old: Optional[float], x: float, alpha: float = 0.5) -> float:
    return x if old is None else (1.0 - alpha) * old + alpha * x


@dataclasses.dataclass
class _Step:
    """A dispatched decode step whose tokens the host has not read yet."""

    tokens: Any                  # (lanes,) int32, where the step left them
    logits: Any                  # (lanes, vocab) rows, or None
    lanes: Dict[int, Request]    # the requests it advances, by lane
    at: Tuple[float, float]      # (dispatch time, prefill seconds by then)


class _Part:
    """One timed part of the tick (prefill, dispatch, wait) as a context:
    adds its nanoseconds to the account's, under its annotation while a
    profiler session records."""

    __slots__ = ("acct", "index", "name", "t0", "annotation")

    def __init__(self, acct: "_TickAccount", index: int):
        self.acct, self.index = acct, index
        self.name = "engine." + TICK_PARTS[index]

    def __enter__(self) -> None:
        prof = self.acct.profiler
        self.annotation = (None if prof is None
                           else open_annotation(self.name, prof))
        self.t0 = time.perf_counter_ns()

    def __exit__(self, *exc) -> None:
        self.acct.ns[self.index] += time.perf_counter_ns() - self.t0
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)


class _TickAccount:
    """The host's time a tick by part (module docstring), and the ring of
    the ticks booked. ``admit`` runs from the tick's start to the end of
    admission, less the prefills in it; ``book`` is the rest of the tick
    less dispatch and wait, so the five add up to the tick exactly. A wait
    outside a tick (``extract``, ``drain`` reading the step in flight) is
    dropped by the next tick's start."""

    def __init__(self, size: int):
        self.ring = np.zeros(size, _TICK_RECORD)
        self.booked = 0           # ticks booked, all time
        self.ns = [0] * len(TICK_PARTS)
        self.profiler = None      # jax.profiler while a session records
        self.lanes = self.prefills = 0
        self.ahead = False
        self.prefill, self.dispatch, self.wait = (
            _Part(self, i) for i in (_PREFILL, _DISPATCH, _WAIT))
        self._start = self._admitted = 0
        self._annotation = None   # the open engine.admit / engine.book

    def _open(self, part: int) -> None:
        if self.profiler is not None:
            self._annotation = open_annotation(
                "engine." + TICK_PARTS[part], self.profiler)

    def close(self) -> None:
        """Close the open ``engine.admit`` / ``engine.book`` annotation."""
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

    def begin(self) -> None:
        self._start = time.perf_counter_ns()
        self.ns = [0] * len(TICK_PARTS)
        self.lanes = 0
        self.ahead = False
        self.profiler = profiling()
        self._open(_ADMIT)

    def admitted(self, prefills: int) -> None:
        """Admission is over, with ``prefills`` run: the rest of the tick
        is decode and book."""
        self.close()
        self._admitted = time.perf_counter_ns()
        self.prefills = prefills

    def book(self) -> None:
        """Open ``engine.book`` over bookkeeping to come (time is booked
        by difference; this is the annotation alone)."""
        self._open(_BOOK)

    def end(self) -> None:
        self.close()
        end = time.perf_counter_ns()
        ns, start = self.ns, self._start
        ns[_ADMIT] = self._admitted - start - ns[_PREFILL]
        ns[_BOOK] = end - self._admitted - ns[_DISPATCH] - ns[_WAIT]
        self.ring[self.booked % len(self.ring)] = (
            start, *ns, self.lanes, self.prefills, self.ahead)
        self.booked += 1

    def log(self) -> Dict[str, np.ndarray]:
        size = len(self.ring)
        kept = np.arange(max(0, self.booked - size), self.booked) % size
        rows = self.ring[kept]
        return {name: rows[name].copy() for name in _TICK_RECORD.names}


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Engine geometry and admission policy (docs/serving.md).

    ``lanes`` bounds concurrent in-flight decodes; ``num_blocks`` x
    ``block_size`` tokens is the whole KV pool; ``max_seq_len`` caps one
    request's prompt+generation (and is what a lane's block table spans,
    so it must divide into blocks). ``prefill_buckets`` (derived
    when None: block-size multiples doubling up to ``max_seq_len``) are
    the ONLY prompt shapes ever compiled. ``ttft_budget_s`` arms the
    admission-time TTFT estimate — beyond it, submissions shed with
    ``ttft_budget`` instead of queueing into a deadline they cannot
    meet. ``top_k``/``top_p`` are engine-static (they shape the
    compiled sort/cumsum); per-request ``temperature`` is traced.
    ``collect_logits`` keeps each request's per-step next-token logits
    on the host (tests/debug; a per-tick vocab-sized fetch).
    ``memory_interval_ticks`` is the cadence of the HBM x-ray's
    ``kind="memory"`` KV-pool records (occupancy + fragmentation,
    monitor.xray.hbm.live.kv_pool_fields); None disables them.
    """

    lanes: int = 4
    block_size: int = 16
    num_blocks: int = 64
    max_seq_len: int = 128
    prefill_buckets: Optional[Tuple[int, ...]] = None
    max_queue_depth: int = 16
    ttft_budget_s: Optional[float] = None
    default_deadline_s: Optional[float] = None
    max_prefills_per_tick: int = 1
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: int = 0
    collect_logits: bool = False
    memory_interval_ticks: Optional[int] = 50

    def __post_init__(self):
        if self.lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {self.lanes}")
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}")
        if self.max_seq_len % self.block_size:
            raise ValueError(
                f"max_seq_len ({self.max_seq_len}) must divide into "
                f"block_size ({self.block_size}) blocks"
            )
        if self.num_blocks < self.max_seq_len // self.block_size:
            raise ValueError(
                f"num_blocks ({self.num_blocks}) cannot hold even one "
                f"max_seq_len ({self.max_seq_len}) request "
                f"({self.max_seq_len // self.block_size} blocks)"
            )
        if self.max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1, got {self.max_queue_depth}")
        if self.max_prefills_per_tick < 1:
            raise ValueError(
                f"max_prefills_per_tick must be >= 1, got "
                f"{self.max_prefills_per_tick}")
        if (self.memory_interval_ticks is not None
                and self.memory_interval_ticks < 1):
            raise ValueError(
                f"memory_interval_ticks must be >= 1 or None, got "
                f"{self.memory_interval_ticks}")
        buckets = self.prefill_buckets
        if buckets is None:
            buckets, b = [], self.block_size
            while b < self.max_seq_len:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_seq_len)
        buckets = tuple(sorted(set(int(b) for b in buckets)))
        for b in buckets:
            if b < 1 or b > self.max_seq_len or b % self.block_size:
                raise ValueError(
                    f"prefill bucket {b} must be a block_size "
                    f"({self.block_size}) multiple in [1, max_seq_len "
                    f"({self.max_seq_len})]"
                )
        object.__setattr__(self, "prefill_buckets", buckets)

    @property
    def max_blocks_per_lane(self) -> int:
        return self.max_seq_len // self.block_size


class ServingEngine:
    """The tick-loop scheduler (module docstring).

    Drive it::

        eng = ServingEngine(model, variables, ServingConfig(...),
                            router=router, fault_plan=plan,
                            watchdog=responder)
        eng.start()                      # AOT-compiles every bucket
        req = eng.submit(prompt, max_new_tokens=32)   # queued/rejected
        while not eng.idle:
            eng.tick()
        eng.drain(grace_s=...)           # on a termination notice

    ``router`` receives the ``kind="request"`` lifecycle records and the
    prefill/decode/drain goodput spans; ``watchdog`` (a StallWatchdog or
    IncidentResponder) is beaten once per tick; ``fault_plan`` injects
    the serving chaos faults. Single-process data plane: the engine
    drives the model with plain ``apply`` (no mesh) — model-parallel
    serving composes later, the robustness contract first.
    """

    def __init__(self, model, variables, config: ServingConfig,
                 router=None, fault_plan=None, watchdog=None,
                 time_fn=time.monotonic):
        self.model = model
        self.variables = variables
        self.config = config
        self.router = router
        self.fault_plan = fault_plan
        self.watchdog = watchdog
        self.time_fn = time_fn
        #: the request x-ray's span producer; the fleet stamps ``site``
        #: with the replica incarnation so span ids stay unique across
        #: restarts (trace/emit.py)
        self.trace = TraceEmitter(router, time_fn=time_fn)
        self._validate_model()

        self.allocator = BlockAllocator(config.num_blocks)
        self._queue: "collections.deque[Request]" = collections.deque()
        self._active: Dict[int, Request] = {}
        self._requests: Dict[int, Request] = {}
        self._next_rid = 0
        self._tick = 0
        self._draining = False
        self._drain_report: Optional[dict] = None
        self._started = False
        self._prefill_ema: Optional[float] = None
        self._decode_ema: Optional[float] = None
        self._steady_compiles = 0
        self._decode_ticks = 0
        self._decode_keys = 0  # keys the lanes' lengths covered, all ticks
        self._decode_ahead = 0  # steps dispatched with the last one unread
        self._acct = _TickAccount(TICK_LOG_TICKS)
        self._compile_watch = None
        self._spec: Optional[CacheSpec] = None
        self._pool = None
        self._prefill_c: Dict[int, Any] = {}
        self._decode_c = None
        self._prefill_key = None
        self._keys = None

        B, MB = config.lanes, config.max_blocks_per_lane
        self._tables = np.full((B, MB), config.num_blocks, np.int32)
        self._positions = np.zeros((B,), np.int32)
        self._last_tok = np.zeros((B,), np.int32)
        self._temps = np.zeros((B,), np.float32)
        self._lane_mask = np.zeros((B,), bool)
        # the next decode step's token inputs are the last step's output
        # where it lies; a lane placed since then (prefill, adopt) is
        # FRESH and reads its token from _last_tok on the host
        self._tok_src = np.zeros((B,), np.int32)
        self._fresh = np.zeros((B,), bool)
        self._inflight: Optional[_Step] = None
        # the decode EMA's clock: (when the last step's tokens were read,
        # prefill seconds by then), and the prefill seconds so far
        self._read_at = (0.0, 0.0)
        self._prefill_wall = 0.0

    # -- model validation ---------------------------------------------------

    def _validate_model(self) -> None:
        cfg = getattr(self.model, "config", None)
        max_pos = getattr(cfg, "max_position_embeddings", None)
        # rope models may leave the field at 0 (no position table); a
        # learned-position table smaller than the serving capacity would
        # CLAMP out-of-range gathers into garbage — refuse at build, the
        # models.generate._check_position_bound contract
        if max_pos and self.config.max_seq_len > max_pos:
            raise ValueError(
                f"max_seq_len ({self.config.max_seq_len}) exceeds the "
                f"model's max_position_embeddings ({max_pos}) — serving "
                f"beyond the position table would emit clamped garbage"
            )
        self._vocab = getattr(cfg, "vocab_size", None)

    # -- compilation (all of it happens here) -------------------------------

    def lower_programs(self, sharding=None) -> Dict[Any, Any]:
        """Lower every program of the engine's life: one prefill per
        bucket (keyed by bucket length) and the decode step (keyed
        ``"decode"``). Also derives the cache layout (``self._spec``).

        ``sharding`` places the abstract arguments; None means the default
        device. The compile-only pre-flight (benchmarks/tpu_preflight.py)
        passes a device of a TPU topology with no chip attached, to compile
        exactly these programs before any chip time is spent."""
        import jax
        import jax.numpy as jnp

        cfg = self.config

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        var_sds = jax.tree_util.tree_map(
            lambda x: sds(x.shape, x.dtype), self.variables
        )
        b0 = cfg.prefill_buckets[0]

        def _prefill_shape(variables, tokens):
            return self.model.apply(
                variables, tokens, cache_len=b0, mutable=["cache"]
            )

        _, shapes = jax.eval_shape(
            _prefill_shape, var_sds, sds((1, b0), jnp.int32)
        )
        self._spec = CacheSpec.from_cache_shapes(shapes["cache"])
        pool_sds = {
            k: sds(shape, dtype)
            for k, (shape, dtype) in self._spec.pool_shapes(
                cfg.num_blocks, cfg.block_size).items()
        }
        i32, f32 = jnp.int32, jnp.float32
        lowered = {}
        for P in cfg.prefill_buckets:
            lowered[P] = jax.jit(
                self._make_prefill(P), donate_argnums=(0,)
            ).lower(
                pool_sds, var_sds, sds((P,), i32), sds((), i32),
                sds((P // cfg.block_size,), i32), sds((), f32),
                sds((2,), jnp.uint32),
            )
        B, MB = cfg.lanes, cfg.max_blocks_per_lane
        lowered["decode"] = jax.jit(
            self._make_decode(), donate_argnums=(0,)
        ).lower(
            pool_sds, var_sds, sds((B, MB), i32), sds((B,), i32),
            sds((B,), i32), sds((B,), f32), sds((B, 2), jnp.uint32),
            sds((B,), jnp.bool_),
        )
        return lowered

    def start(self) -> "ServingEngine":
        """Build the pool and AOT-compile every prefill bucket plus the
        decode step. Every compile of the engine's life happens inside
        this call (booked as a ``compile`` goodput span); the
        CompileWatcher created at the end then counts any later compile
        as a steady-state violation."""
        if self._started:
            return self
        import jax

        cfg = self.config
        with span("compile", router=self.router, step=-1):
            # the weights live on the device ONCE and enter every program
            # as an ARGUMENT: closed over, each of the bucket programs and
            # the decode step would carry its own copy of the model as a
            # constant (a 345M-parameter model is 1.4 GB per program, in
            # HBM, in the compile and in the persistent cache)
            self.variables = jax.device_put(self.variables)
            for key, lowered in self.lower_programs().items():
                if key == "decode":
                    self._decode_c = lowered.compile()
                else:
                    self._prefill_c[key] = lowered.compile()
            self._pool = {
                k: jax.device_put(np.zeros(shape, dtype))
                for k, (shape, dtype) in self._spec.pool_shapes(
                    cfg.num_blocks, cfg.block_size).items()
            }
            self._prefill_key = jax.random.PRNGKey(cfg.seed)
            self._keys = jax.random.split(
                jax.random.PRNGKey(cfg.seed + 1), cfg.lanes
            )
        from apex_tpu.monitor.xray.compile_watch import CompileWatcher

        self._compile_watch = CompileWatcher(router=self.router)
        self._started = True
        logger.info(
            "serving engine ready: %d lanes, %d blocks x %d tokens, "
            "buckets %s", cfg.lanes, cfg.num_blocks, cfg.block_size,
            cfg.prefill_buckets,
        )
        return self

    def _make_prefill(self, P: int):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models.generate import sample_next_token

        cfg, spec, model = self.config, self._spec, self.model

        def prefill(pool, variables, tokens, true_len, block_ids, temp, key):
            logits, st = model.apply(
                variables, tokens[None], cache_len=P, mutable=["cache"]
            )
            # next-token logits at the TRUE prompt end; the right-padded
            # tail is causal-shadowed (positions >= true_len never feed
            # position true_len - 1)
            last = jax.lax.dynamic_index_in_dim(
                logits[0].astype(jnp.float32), true_len - 1, axis=0,
                keepdims=False,
            )
            key, sub = jax.random.split(key)
            tok = sample_next_token(
                last, temp, sub, top_k=cfg.top_k, top_p=cfg.top_p
            )
            kv = spec.kv_from_cache(st["cache"])
            new_pool = dict(pool)
            for k, leaf in kv.items():
                # out-of-range sentinel ids drop their (unreserved,
                # fully-padded) blocks on the scatter
                new_pool[k] = pool[k].at[block_ids].set(
                    spec.to_blocks(leaf, cfg.block_size).astype(
                        pool[k].dtype), mode="drop"
                )
            out = (new_pool, tok.astype(jnp.int32), key)
            if cfg.collect_logits:
                out = out + (last,)
            return out

        return prefill

    def _make_decode(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models.generate import sample_next_token

        cfg, spec, model = self.config, self._spec, self.model

        def decode(pool, variables, tables, positions, tokens, temps, keys,
                   active):
            # ONE call of the model for all lanes, over the pool where it
            # lies (kvcache.py): every attention layer writes each lane's
            # new key and value into its slot and attends by table and
            # length. Inactive lanes compute garbage (static batch); an
            # all-sentinel table drops their writes
            tables = jnp.where(active[:, None], tables, cfg.num_blocks)
            logits, upd = model.apply(
                {**variables,
                 "cache": spec.paged_cache(pool, tables, positions)},
                tokens[:, None],
                position_ids=positions[:, None],
                cache_len=cfg.max_seq_len,
                decode_step=True,
                mutable=["cache"],
            )
            last = logits[:, 0].astype(jnp.float32)
            split = jax.vmap(jax.random.split)(keys)
            nxts = jax.vmap(
                lambda row, temp, sub: sample_next_token(
                    row, temp, sub, top_k=cfg.top_k, top_p=cfg.top_p)
            )(last, temps, split[:, 1])
            out = (spec.pool_from_cache(upd["cache"]),
                   nxts.astype(jnp.int32), split[:, 0])
            if cfg.collect_logits:
                out = out + (last,)
            return out

        return decode

    # -- admission ----------------------------------------------------------

    def _validate_submission(self, prompt, max_new_tokens, temperature,
                             deadline_s) -> Tuple[
            Optional[np.ndarray], int, float, Optional[float],
            Optional[str], Optional[str]]:
        """(prompt_array, max_new, temperature, deadline_s, reason,
        detail) — reason None = valid. On invalid input the parsed
        fields fall back to inert defaults so the rejected Request
        still constructs: ``submit`` NEVER raises on bad client input,
        it sheds with a reason."""
        def bad(detail, reason="malformed"):
            return None, 1, 0.0, None, reason, detail

        try:
            n_new = int(max_new_tokens)
        except (TypeError, ValueError):
            return bad(f"max_new_tokens {max_new_tokens!r} is not an "
                       f"integer")
        try:
            temp = float(temperature)
        except (TypeError, ValueError):
            return bad(f"temperature {temperature!r} is not a number")
        try:
            ddl = None if deadline_s is None else float(deadline_s)
        except (TypeError, ValueError):
            return bad(f"deadline_s {deadline_s!r} is not a number")
        try:
            arr = np.asarray(prompt)
        except Exception:
            return bad("prompt is not array-like")
        if arr.ndim != 1 or arr.size == 0:
            return bad(f"prompt must be a nonempty 1-d token array, got "
                       f"shape {arr.shape}")
        if not np.issubdtype(arr.dtype, np.integer):
            return bad(f"prompt dtype {arr.dtype} not integer")
        if self._vocab and (arr.min() < 0 or arr.max() >= self._vocab):
            return bad(f"prompt token out of vocab [0, {self._vocab})")
        if n_new < 1:
            return bad(f"max_new_tokens must be >= 1, got {n_new}")
        cfg = self.config
        if arr.size > cfg.prefill_buckets[-1]:
            return bad(
                f"prompt ({arr.size}) exceeds the largest prefill bucket "
                f"({cfg.prefill_buckets[-1]})", reason="too_long")
        if arr.size + n_new > cfg.max_seq_len:
            return bad(
                f"prompt ({arr.size}) + max_new_tokens ({n_new}) exceeds "
                f"max_seq_len ({cfg.max_seq_len})", reason="too_long")
        return arr.astype(np.int32), n_new, temp, ddl, None, None

    def estimated_ttft_s(self) -> Optional[float]:
        """Admission-time TTFT estimate for a NEW submission: queue depth
        x the measured per-admission cost (prefill + one decode tick,
        EMAs; the decode EMA is the period between two steps' reads, less
        the prefills run in it), scaled by the per-tick admission width.
        None until the first prefill measured (the budget arms with the
        estimator)."""
        if self._prefill_ema is None:
            return None
        per = self._prefill_ema + (self._decode_ema or 0.0)
        width = max(1, self.config.max_prefills_per_tick)
        return (len(self._queue) + 1) * per / width

    def submit(self, prompt, max_new_tokens: int,
               temperature: float = 0.0,
               deadline_s: Optional[float] = None,
               rid: Optional[int] = None,
               tags: Optional[dict] = None) -> Request:
        """Admission control at the door (module docstring): the request
        is QUEUED, or REJECTED with a booked reason — this method never
        raises on bad input and never buffers beyond the bounds.

        ``rid`` lets a fleet router supply a GLOBALLY unique request id
        (the stream's closure assertion keys on ``id``, so engine-local
        counters would collide across replicas); ``tags`` are merged
        into every record the request emits (lifecycle.Request.tags —
        replica placement, prefix-cache hit rate, re-dispatch attempt).
        """
        self._ensure_started()
        now = self.time_fn()
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            rid = int(rid)
            self._next_rid = max(self._next_rid, rid + 1)
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        arr, n_new, temp, ddl, bad_reason, detail = (
            self._validate_submission(
                prompt, max_new_tokens, temperature, deadline_s))
        req = Request(
            rid=rid, prompt=arr, max_new_tokens=max(n_new, 1),
            temperature=temp, deadline_s=ddl, submit_t=now,
            tags=dict(tags) if tags else {},
        )
        self._requests[rid] = req

        def reject(reason, **extra):
            transition(req, REJECTED, now=now, reason=reason)
            emit_request_record(self.router, self._tick, req,
                                trace=self.trace, **extra)
            logger.warning("request %d rejected (%s)%s", rid, reason,
                           f": {detail}" if detail else "")
            return req

        if self._draining:
            return reject("draining")
        if bad_reason is not None:
            return reject(bad_reason, detail=detail)
        # TTFT estimate first: it is the stronger signal (a shallow queue
        # over a slow engine is still an unmeetable wait); the depth
        # bound is the fallback for the cold window before EMAs exist
        est = self.estimated_ttft_s()
        if (self.config.ttft_budget_s is not None and est is not None
                and est > self.config.ttft_budget_s):
            return reject("ttft_budget", estimated_ttft_s=est)
        if len(self._queue) >= self.config.max_queue_depth:
            return reject("queue_full")
        transition(req, QUEUED, now=now)
        self._queue.append(req)
        emit_request_record(self.router, self._tick, req,
                            trace=self.trace)
        return req

    def cancel(self, rid: int) -> bool:
        """Client abandon: evict ``rid`` wherever it is; True if it was
        live (terminal/unknown requests are a no-op)."""
        req = self._requests.get(rid)
        if req is None or req.terminal:
            return False
        if req.state == QUEUED:
            self._queue.remove(req)
            transition(req, CANCELLED, now=self.time_fn(),
                       reason="client_cancel")
            emit_request_record(self.router, self._tick, req,
                                trace=self.trace)
            return True
        self._release(req, CANCELLED, "client_cancel")
        return True

    # -- placement ----------------------------------------------------------

    def _free_lane(self) -> Optional[int]:
        for lane in range(self.config.lanes):
            if lane not in self._active:
                return lane
        return None

    def _bucket_for(self, prompt_len: int) -> int:
        for b in self.config.prefill_buckets:
            if b >= prompt_len:
                return b
        raise AssertionError("validated at submit")  # pragma: no cover

    def _try_place(self, req: Request) -> Optional[
            Tuple[int, Tuple[int, ...], int]]:
        """(lane, blocks, bucket) or None when capacity is short — the
        request then WAITS in the bounded queue (admission shed happens
        at submit; capacity waits are what deadlines bound)."""
        lane = self._free_lane()
        if lane is None:
            return None
        P = self._bucket_for(req.prompt_len)
        cfg = self.config
        # worst case up front (kvcache.py): decode can never deadlock on
        # pool memory mid-request
        need = max(
            blocks_needed(req.prompt_len + req.max_new_tokens,
                          cfg.block_size),
            P // cfg.block_size,
        )
        ids = self.allocator.alloc(need)
        if ids is None:
            return None
        return lane, ids, P

    # -- the tick loop ------------------------------------------------------

    def _ensure_started(self) -> None:
        if not self._started:
            self.start()

    @property
    def idle(self) -> bool:
        return (not self._queue and not self._active
                and self._inflight is None)

    @property
    def steady_state_compiles(self) -> int:
        """Compiles observed AFTER start() finished — the zero-recompile
        contract's violation counter (0 in a healthy steady state)."""
        return self._steady_compiles

    def tick(self) -> int:
        """One scheduler iteration (module docstring); returns the tick
        number just executed."""
        self._ensure_started()
        acct = self._acct
        acct.begin()
        t = self._tick
        now = self.time_fn()
        self._expire(now)
        if self.fault_plan is not None:
            # the wedge fault blocks HERE, inside the loop the watchdog
            # guards — exactly like the training examples inject it
            hang_t0 = self.time_fn()
            self.fault_plan.maybe_hang(t)
            hang_s = self.time_fn() - hang_t0
            if hang_s > 0.0:
                self.trace.stall(t, list(self._active.values()),
                                 hang_t0, hang_s)
        n_pref = 0
        while (self._queue and not self._draining
               and n_pref < self.config.max_prefills_per_tick):
            placement = self._try_place(self._queue[0])
            if placement is None:
                break
            req = self._queue.popleft()
            lane, blocks, P = placement
            req.lane, req.blocks, req.bucket = lane, blocks, P
            transition(req, ADMITTED, now=self.time_fn())
            emit_request_record(self.router, t, req, trace=self.trace)
            self._run_prefill(req, t)
            n_pref += 1
        acct.admitted(n_pref)
        if self._active or self._inflight is not None:
            self._run_decode(t)
        acct.book()
        if self.watchdog is not None:
            self.watchdog.beat(t)
        if self._compile_watch is not None:
            rec = self._compile_watch.on_step(t)
            if rec is not None:
                self._steady_compiles += int(rec.get("compiles", 0))
                logger.warning(
                    "serving steady-state compile at tick %d — a shape "
                    "escaped the AOT buckets", t,
                )
        interval = self.config.memory_interval_ticks
        if (self.router is not None and interval is not None
                and t % interval == 0):
            # the HBM x-ray's serving half: KV-pool occupancy +
            # fragmentation on the same kind="memory" stream the
            # training watermark monitor writes (hbm/live.py)
            from apex_tpu.monitor.xray.hbm.live import kv_pool_fields

            self.router.event("memory", t, **kv_pool_fields(
                num_blocks=self.allocator.num_blocks,
                free_blocks=self.allocator.free_blocks,
                block_size=self.config.block_size,
                live_tokens=sum(
                    int(self._positions[lane]) for lane in self._active
                ),
                peak_used_blocks=self.allocator.peak_used_blocks,
            ))
        self._tick += 1
        acct.end()
        return t

    def _run_prefill(self, req: Request, t: int) -> None:
        cfg = self.config
        transition(req, PREFILL, now=self.time_fn())
        emit_request_record(self.router, t, req, trace=self.trace)
        L, P = req.prompt_len, req.bucket
        n_pb = P // cfg.block_size
        tokens = np.zeros((P,), np.int32)
        tokens[:L] = req.prompt
        block_ids = np.full((n_pb,), cfg.num_blocks, np.int32)
        k = min(n_pb, len(req.blocks))
        block_ids[:k] = req.blocks[:k]
        t0 = time.perf_counter()
        try:
            with span("prefill", router=self.router, step=t), \
                    self._acct.prefill:
                out = self._prefill_c[P](
                    self._pool, self.variables, tokens, np.int32(L),
                    block_ids,
                    np.float32(req.temperature), self._prefill_key,
                )
                self._pool, tok_dev, self._prefill_key = out[:3]
                tok = int(np.asarray(tok_dev))
        except Exception as e:
            logger.exception("prefill failed for request %d", req.rid)
            self.allocator.free(req.blocks)
            transition(req, FAILED, now=self.time_fn(),
                       reason=f"engine_error: {type(e).__name__}")
            emit_request_record(self.router, t, req, trace=self.trace)
            return
        dt = time.perf_counter() - t0
        self._prefill_ema = _ema(self._prefill_ema, dt)
        self._prefill_wall += dt
        req.first_token_t = self.time_fn()
        req.tokens_out.append(tok)
        if cfg.collect_logits:
            req.logits = (req.logits or []) + [np.asarray(out[3])]
        if len(req.tokens_out) >= req.max_new_tokens:
            # single-token request: prefill IS the whole generation
            self.allocator.free(req.blocks)
            transition(req, COMPLETED, now=self.time_fn())
            emit_request_record(self.router, t, req, trace=self.trace)
            return
        transition(req, DECODE, now=self.time_fn())
        emit_request_record(self.router, t, req, trace=self.trace)
        lane = req.lane
        self._tables[lane, :] = cfg.num_blocks
        self._tables[lane, :len(req.blocks)] = req.blocks
        self._positions[lane] = L
        self._last_tok[lane] = tok
        self._fresh[lane] = True
        self._temps[lane] = req.temperature
        self._lane_mask[lane] = True
        self._active[lane] = req

    def _run_decode(self, t: int) -> None:
        """Dispatch decode step t, then read step t-1 (module docstring):
        a lane whose request step t-1's token completes takes no step t."""
        prev = self._inflight
        lanes = self._lane_mask.copy()
        if prev is not None:
            for lane, req in prev.lanes.items():
                if (self._active.get(lane) is req
                        and len(req.tokens_out) + 1 >= req.max_new_tokens):
                    lanes[lane] = False
        try:
            with span("decode", router=self.router, step=t):
                self._acct.book()   # dispatch and wait nest in it
                try:
                    if self.fault_plan is not None:
                        # injected INSIDE the span: the inflated tick is
                        # exactly the span the stall warn flags
                        self.fault_plan.maybe_slow_decode(t)
                    self._inflight = None
                    if lanes.any():
                        self._dispatch_decode(lanes, ahead=prev is not None)
                    if prev is not None:
                        self._read_decode(prev)
                finally:
                    self._acct.close()
        except Exception as e:
            logger.exception("decode tick %d failed", t)
            self._inflight = None
            for req in list(self._active.values()):
                self._release(
                    req, FAILED, f"engine_error: {type(e).__name__}")
            raise

    def _dispatch_decode(self, lanes: np.ndarray, ahead: bool) -> None:
        """Dispatch one decode step for ``lanes`` without waiting for it.
        ``ahead``: the last step's tokens are still unread, and stay so
        unless a fresh lane needs them merged on the host."""
        acct = self._acct
        tokens = self._tok_src
        fresh = lanes & self._fresh
        if fresh.any():
            # a lane placed since the last step joins it: its first token
            # is on the host, so the others' are fetched to sit beside it
            # (the tick's prefill already waited for the last step)
            with acct.wait:
                tokens = np.array(tokens)
            tokens[fresh] = self._last_tok[fresh]
            ahead = False
        self._fresh[lanes] = False
        # what the decode step reads of the pool: every active lane's keys
        # [0, position], and nothing of an idle lane
        self._decode_ticks += 1
        self._decode_ahead += int(ahead)
        self._decode_keys += int(self._positions[lanes].sum() + lanes.sum())
        at = (time.perf_counter(), self._prefill_wall)
        # copies: the host advances its arrays while the step runs
        args = (self._pool, self.variables, self._tables.copy(),
                self._positions.copy(), tokens, self._temps.copy(),
                self._keys, lanes)
        with acct.dispatch:
            out = self._decode_c(*args)
        self._pool, self._tok_src, self._keys = out[:3]
        self._positions[lanes] += 1
        self._inflight = _Step(
            tokens=self._tok_src,
            logits=out[3] if self.config.collect_logits else None,
            lanes={lane: req for lane, req in self._active.items()
                   if lanes[lane]},
            at=at,
        )
        acct.lanes, acct.ahead = len(self._inflight.lanes), ahead

    def _read_decode(self, step: _Step) -> None:
        """Read a dispatched step's tokens into their requests and release
        the requests they complete. A request that left its lane while the
        step was in flight (cancel, deadline, extract) gets no token."""
        with self._acct.wait:
            nxts = np.asarray(step.tokens)
            logits_rows = (np.asarray(step.logits)
                           if step.logits is not None else None)
        now = time.perf_counter()
        # the tick's period since the later of this step's dispatch and
        # the last read, less the prefills run in it
        start, prefill_then = max(step.at, self._read_at)
        self._decode_ema = _ema(
            self._decode_ema,
            now - start - (self._prefill_wall - prefill_then))
        self._read_at = (now, self._prefill_wall)
        for lane, req in step.lanes.items():
            if self._active.get(lane) is not req:
                continue
            tok = int(nxts[lane])
            req.tokens_out.append(tok)
            self._last_tok[lane] = tok
            if logits_rows is not None:
                req.logits = (req.logits or []) + [logits_rows[lane]]
            if len(req.tokens_out) >= req.max_new_tokens:
                self._release(req, COMPLETED, None)

    def _settle(self) -> None:
        """Read the decode step in flight, if any, dispatching none."""
        step, self._inflight = self._inflight, None
        if step is not None:
            self._read_decode(step)

    def _release(self, req: Request, state: str,
                 reason: Optional[str]) -> None:
        """Evict ``req`` from its lane, reclaim its blocks, book the
        terminal state — the ONE eviction path, so blocks can never
        leak past an ending."""
        lane = req.lane
        if lane is not None and self._active.get(lane) is req:
            self._vacate(lane)
        self.allocator.free(req.blocks)
        transition(req, state, now=self.time_fn(), reason=reason)
        emit_request_record(self.router, self._tick, req,
                            trace=self.trace)

    def _vacate(self, lane: int) -> None:
        del self._active[lane]
        self._lane_mask[lane] = False
        self._tables[lane, :] = self.config.num_blocks
        self._positions[lane] = 0
        self._last_tok[lane] = 0
        self._fresh[lane] = False
        self._temps[lane] = 0.0

    def _expire(self, now: float) -> None:
        """Deadline enforcement, EVERY tick, queue and batch alike."""
        for req in [r for r in self._queue
                    if r.expires_at() is not None
                    and now > r.expires_at()]:
            self._queue.remove(req)
            transition(req, TIMED_OUT, now=now, reason="deadline")
            emit_request_record(self.router, self._tick, req,
                                trace=self.trace)
        for req in [r for r in self._active.values()
                    if r.expires_at() is not None
                    and now > r.expires_at()]:
            self._release(req, TIMED_OUT, "deadline")

    # -- fleet KV handoff (extract/adopt) -----------------------------------

    def extract(self, rid: int) -> Optional[dict]:
        """Remove a mid-decode request from this engine WITHOUT booking
        a terminal state, returning a handoff payload ``adopt`` can
        install on another replica (the fleet's prefill/decode
        disaggregation; docs/serving.md "Fleet").

        The payload carries the request object, its lane's decode
        cursor (position, last sampled token) and the request's KV
        block CONTENTS as host arrays — a pure device-to-host read, no
        compiled ops, so the zero-recompile contract holds across a
        handoff. Returns None unless ``rid`` is live in a decode lane
        (queued/terminal requests have nothing to hand off). The lane
        and blocks are reclaimed here; the request leaves this engine's
        books entirely — its lifecycle continues on the adopter. The
        decode step in flight is read first, so that the handed-over
        cache and ``tokens_out`` agree (a request that step completes
        ends here, and None is returned).
        """
        self._settle()
        req = self._requests.get(rid)
        if req is None or req.state != DECODE or req.lane is None:
            return None
        lane = req.lane
        if self._active.get(lane) is not req:
            return None
        ids = list(req.blocks)
        kv = {}
        nbytes = 0
        for k in self._pool:
            host = np.array(np.asarray(self._pool[k])[ids])
            kv[k] = host
            nbytes += host.nbytes
        payload = {
            "request": req,
            "position": int(self._positions[lane]),
            "last_token": int(self._last_tok[lane]),
            "kv": kv,
            "n_blocks": len(ids),
            "bytes": int(nbytes),
        }
        self._vacate(lane)
        self.allocator.free(req.blocks)
        req.lane, req.blocks = None, ()
        del self._requests[rid]
        # the request's decode segment on THIS engine ends here; its
        # story continues on the adopter (or at the fleet)
        self.trace.extracted(self._tick, req)
        return payload

    def adopt(self, payload: dict) -> bool:
        """Install an ``extract`` payload into a free lane of THIS
        engine: allocate blocks, scatter the handed-off KV contents
        into the pool (host round-trip + ``device_put`` — no compiled
        ops, so no steady-state compile), and resume the decode cursor
        exactly where the source left it. False when this engine cannot
        take it (no free lane, pool short, rid already present, or a
        mismatched pool geometry) — the caller then tries another
        replica or re-queues; the request object is untouched on
        refusal, so adoption is all-or-nothing like ``alloc``.

        Greedy (temperature 0) decode resumes bit-identically — the KV
        bytes are the whole cursor; sampled decode resumes on the
        adopting lane's OWN rng stream (per-lane keys are engine
        state, not request state).
        """
        self._ensure_started()
        req: Request = payload["request"]
        if req.rid in self._requests or req.state != DECODE:
            return False
        first = next(iter(payload["kv"].values()))
        if (set(payload["kv"]) != set(self._pool)
                or first.shape[1:] != next(
                    iter(self._pool.values())).shape[1:]):
            return False
        lane = self._free_lane()
        if lane is None:
            return False
        ids = self.allocator.alloc(payload["n_blocks"])
        if ids is None:
            return False
        import jax

        for k, blocks in payload["kv"].items():
            host = np.array(np.asarray(self._pool[k]))
            host[list(ids)] = blocks
            self._pool[k] = jax.device_put(host)
        req.lane, req.blocks = lane, ids
        self._requests[req.rid] = req
        self._active[lane] = req
        self._tables[lane, :] = self.config.num_blocks
        self._tables[lane, :len(ids)] = ids
        self._positions[lane] = payload["position"]
        self._last_tok[lane] = payload["last_token"]
        self._fresh[lane] = True
        self._temps[lane] = req.temperature
        self._lane_mask[lane] = True
        self.trace.adopted(self._tick, req)
        return True

    def acknowledge_compiles(self) -> None:
        """Re-anchor the compile watcher after a BOOKED external
        compile burst: the jax compile counter is process-global, so a
        fleet scale-up compiling a NEW replica's buckets in-process
        would otherwise land on every SURVIVOR's violation counter.
        The burst is booked as the new replica's own ``compile`` span;
        only unbooked compiles are steady-state violations."""
        if self._compile_watch is not None:
            self._compile_watch.rebaseline()

    # -- drain --------------------------------------------------------------

    def drain(self, grace_s: Optional[float] = None,
              deadline: Optional[float] = None) -> dict:
        """Graceful drain: stop admitting, reject the still-queued,
        finish or deadline-evict the in-flight within the grace budget,
        and emit a terminal state for EVERY request (module docstring).

        ``deadline`` is an absolute monotonic instant (the
        ``TerminationNotice.grace_deadline()`` convention); ``grace_s``
        is relative from now. With neither, the drain runs until the
        batch empties (deadlines on the requests themselves still
        apply). Returns a summary dict.

        Re-entrant by contract: a SECOND drain call returns the first
        drain's summary marked ``redundant=True`` — it never re-runs
        the reject loop, re-opens a drain span, or raises (a fleet
        scale-down and a SIGTERM racing to drain the same replica must
        both get a closed answer). ``submit`` after drain likewise
        sheds with a booked ``draining`` rejection, never an exception.
        """
        self._ensure_started()
        if self._drain_report is not None:
            return dict(self._drain_report, redundant=True)
        self._draining = True
        t0 = self.time_fn()
        if deadline is None and grace_s is not None:
            deadline = t0 + grace_s
        inflight0 = list(self._active.values())
        evicted = 0
        with span("drain", router=self.router, step=self._tick):
            while self._queue:
                req = self._queue.popleft()
                transition(req, REJECTED, now=self.time_fn(),
                           reason="draining")
                emit_request_record(self.router, self._tick, req,
                                    trace=self.trace)
            while self._active:
                if deadline is not None and self.time_fn() > deadline:
                    for req in list(self._active.values()):
                        self._release(req, TIMED_OUT, "drain_deadline")
                        evicted += 1
                    break
                self.tick()
            # a step still in flight advances no live request now: its
            # tokens are dropped
            self._settle()
        # summarize by the ACTUAL endings of the requests that were in
        # flight at drain start — a request whose OWN deadline expired
        # inside the window is a timeout, not a finish; the jsonl stream
        # is the ground truth this summary must not contradict
        finished = sum(1 for r in inflight0 if r.state == COMPLETED)
        timed_out = sum(1 for r in inflight0
                        if r.state == TIMED_OUT
                        and r.reason != "drain_deadline")
        out = {
            "drain_s": self.time_fn() - t0,
            "finished": finished,
            "evicted": evicted,
            "timed_out": timed_out,
        }
        self._drain_report = dict(out)
        logger.info(
            "drain complete in %.3fs: %d finished, %d deadline-evicted, "
            "%d timed out on their own deadlines",
            out["drain_s"], finished, evicted, timed_out,
        )
        return out

    @property
    def draining(self) -> bool:
        return self._draining

    # -- introspection ------------------------------------------------------

    def inflight_table(self) -> dict:
        """The forensic in-flight table for the incident bundle
        (``IncidentResponder(bundle_extra=engine.inflight_table)``):
        lock-free best-effort reads only."""
        rows = []
        for lane, req in list(self._active.items()):
            rows.append({
                "id": req.rid, "lane": lane, "state": req.state,
                "prompt_len": req.prompt_len,
                "tokens_out": len(req.tokens_out),
                "max_new": req.max_new_tokens,
                "deadline_s": req.deadline_s,
            })
        return {
            "requests": rows,
            "queued": len(self._queue),
            "tick": self._tick,
            "free_blocks": self.allocator.free_blocks,
        }

    def requests(self) -> List[Request]:
        return list(self._requests.values())

    def tick_log(self) -> Dict[str, np.ndarray]:
        """The host's account of the ticks kept (module docstring), oldest
        first, one entry a tick in each array: ``start_ns`` (the tick's
        start, ``time.perf_counter_ns``), ``<part>_ns`` for each of
        ``TICK_PARTS`` (they add up to the tick's wall time), ``lanes``
        (the lanes its decode step advanced, 0 if it dispatched none),
        ``prefills`` and ``ahead`` (its step was dispatched with the last
        one's tokens unread). A copy."""
        return self._acct.log()

    def stats(self) -> dict:
        """Aggregate serving outcome (docs/serving.md): per-terminal
        counts, shed reasons, TTFT percentiles over requests that got a
        first token, the zero-recompile violation counter,
        ``decode_keys_read_share``: over the decode ticks so far, the keys
        the lanes' lengths covered over ``lanes * max_seq_len``, i.e. the
        share of a fixed full window per lane that decode attention, which
        stops at each lane's length, had to read (None before the first
        decode tick), ``decode_dispatched_ahead_share``: the decode steps dispatched
        while the previous step's tokens were still unread, over all
        decode steps (None before the first), and ``host_tick_ms``: the
        median milliseconds of each of ``TICK_PARTS`` and of the whole
        ``tick`` over the ticks :meth:`tick_log` keeps (None before the
        first tick)."""
        from apex_tpu.serving.loadgen import percentile

        counts: Dict[str, int] = {}
        reasons: Dict[str, int] = {}
        ttfts: List[float] = []
        tokens = 0
        live = 0
        for req in self._requests.values():
            if req.terminal:
                counts[req.state] = counts.get(req.state, 0) + 1
                if req.reason:
                    reasons[req.reason] = reasons.get(req.reason, 0) + 1
            else:
                live += 1
            if req.ttft_s is not None:
                ttfts.append(req.ttft_s)
            tokens += len(req.tokens_out)
        return {
            "submitted": self._next_rid,
            "live": live,
            "terminal": counts,
            "reasons": reasons,
            "tokens_out": tokens,
            "ttft_p50_s": percentile(ttfts, 50.0),
            "ttft_p99_s": percentile(ttfts, 99.0),
            "prefill_ema_s": self._prefill_ema,
            "decode_ema_s": self._decode_ema,
            "ticks": self._tick,
            "steady_state_compiles": self._steady_compiles,
            "free_blocks": self.allocator.free_blocks,
            "kv_pool_peak_blocks": self.allocator.peak_used_blocks,
            "decode_keys_read_share": (
                self._decode_keys / (self._decode_ticks * self.config.lanes
                                     * self.config.max_seq_len)
                if self._decode_ticks else None),
            "decode_dispatched_ahead_share": (
                self._decode_ahead / self._decode_ticks
                if self._decode_ticks else None),
            "host_tick_ms": self._host_tick_ms(),
        }

    def _host_tick_ms(self) -> Optional[Dict[str, float]]:
        log = self._acct.log()
        if not len(log["start_ns"]):
            return None
        parts = {p: log[p + "_ns"] for p in TICK_PARTS}
        parts["tick"] = sum(parts.values())
        return {p: float(np.median(ns)) / 1e6 for p, ns in parts.items()}
