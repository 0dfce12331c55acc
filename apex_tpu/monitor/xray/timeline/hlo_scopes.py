"""Whose is this device op? The compiled step's text, joined to the
program's names.

A device event of a TPU capture names an HLO instruction
(``fusion.895``, ``copy.2098``, ``self_attention.117``) and nothing of the
program. The compiled module knows more: each instruction's ``metadata``
keeps the ``op_name`` path it was traced under —

    jit(train_step)/forward_backward/transpose(jvp(vmap(GPTModel)))/
        transformer/layer_3/mlp/dense_h_to_4h/dot_general

— the step's phase (``goodput.scopes.STEP_PHASES``, a ``jax.named_scope``
in ``apex_tpu/training/gpt_step.py``), JAX's own ``transpose(...)`` on
every op of the backward pass, the flax module path, the primitive. And a
Pallas kernel's custom-call carries its registered name in
``kernel_metadata`` (``goodput.scopes.KERNELS``). :func:`scope_map` reads
all of it into one table, instruction name -> :class:`OpScope`, which the
analyzer joins to the capture's events by name.

Not every instruction has metadata of its own: copies the compiler put in
for a layout, the prefetches of memory-space assignment. They are
attributed in this order, and :attr:`OpScope.how` says which applied:

- ``own``     — the instruction's own ``op_name``;
- ``fused``   — a fusion belongs where most of what it fused was traced:
  the commonest phase among the instructions of the computation it
  calls, a tie going to the fusion's own ``op_name`` (the root's, which
  XLA copied up). XLA fuses across the program's phases — on a v5e the
  Adam update and the non-finite check of the new parameters are ONE
  pass over the parameters, labelled with the check's name — so a
  fusion's own name alone would book the update to ``guard``.
  :attr:`OpScope.mix` keeps what else such a fusion holds: its time
  cannot be split, and the report says how much time sits in fusions
  that span phases;
- ``caller``  — an instruction inside a called computation (a ``cond``
  branch, a ``while`` body, a fused computation): the scope of the
  instruction that calls it. Exact: the body runs because, and where,
  the caller was traced;
- ``flow``    — the scope of the nearest user, else of the nearest
  producer, that has one (a metadata-less ``copy`` belongs to the op it
  feeds). An inference, reported apart so it can be doubted;
- ``none``    — nothing applied (parameters, constants, tuples).

jax-free: the text comes through ``analysis/hlo/parser.py`` (the one home
of HLO text parsing), the registries from ``goodput/scopes.py``.
"""

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from apex_tpu.analysis.hlo.parser import (
    HloInstruction,
    HloModule,
    parse_hlo_module,
)
from apex_tpu.monitor.goodput.scopes import (
    KERNEL_KEY,
    KERNELS,
    MODEL_SCOPES,
    STEP_PHASES,
)

__all__ = [
    "OpScope",
    "UNATTRIBUTED",
    "FORWARD",
    "BACKWARD",
    "split_path",
    "classify_path",
    "kernel_of",
    "tiles_of",
    "scope_map",
    "STRUCTURAL",
]

FORWARD = "forward"
BACKWARD = "backward"
#: the phase of an op no rule could place
UNATTRIBUTED = "(unattributed)"

#: path components that are JAX's, not the model's: control flow and call
#: wrappers without parentheses (those with — ``jvp(...)``, ``jit(...)``
#: — are recognised by the parenthesis)
_JAX_FRAMES = frozenset({
    "cond", "while", "body", "scan", "checkpoint", "remat", "closed_call",
    "rematted_computation",
    "core_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "pjit", "shard_map", "pallas_call",
})
_BRANCH = re.compile(r"^branch_\d+_fun$")
#: a scope opened while a transform traced it: ``transpose(jvp(moe_experts))``
_WRAPPED = re.compile(r"^(?:\w+\()+(\w+)\)+$")
_LAYER = re.compile(r"^(.*?_)\d+$")

#: opcodes that occupy no device time of their own and take no scope
STRUCTURAL = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id",
})


@dataclasses.dataclass(frozen=True)
class OpScope:
    """Where one instruction belongs in the program."""

    phase: str  # a STEP_PHASES name, or UNATTRIBUTED
    direction: Optional[str]  # FORWARD / BACKWARD inside forward_backward
    module: str  # flax module path, layer index collapsed; "" = none
    kernel: Optional[str]  # a KERNELS name for a Pallas custom-call
    op_name: str
    how: str  # own | fused | caller | flow | none
    #: for a fusion whose fused instructions span several phases:
    #: ``((phase, instructions), ...)``, most first; else empty
    mix: Tuple[Tuple[str, int], ...] = ()
    #: the tiles a Pallas kernel's call ran (:func:`tiles_of`); "" = none
    tiles: str = ""

    @property
    def part(self) -> str:
        """The phase, split by direction where it has one:
        ``forward_backward`` reads ``forward`` or ``backward``."""
        return self.direction or self.phase


def split_path(op_name: str) -> List[str]:
    """``op_name`` cut at the ``/`` outside parentheses. XLA joins the
    paths of ops it merged with ``;``: the first speaks for all."""
    path = op_name.split(";", 1)[0]
    parts, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    parts.append("".join(cur))
    return [p for p in parts if p]


def classify_path(op_name: str) -> Tuple[str, Optional[str], str]:
    """``(phase, direction, module)`` of one ``op_name`` path.

    The phase is the first component that is a registered step phase.
    Direction exists inside ``forward_backward`` only: ``backward`` when
    any component is a ``transpose(...)`` (JAX's mark on the ops of a
    transposed jvp; a ``custom_vjp``'s backward rule is traced under
    ``transpose(<the scope>)``), else ``forward``. The module is what
    lies between the phase and the final primitive once JAX's own frames
    are dropped: transforms (anything with parentheses), control flow,
    ``pallas_call``, a kernel's name; ``layer_7`` reads ``layer_*``. A
    model scope opened under a transform (a ``custom_vjp`` rule that
    differentiates its own forward prints ``jvp(moe_experts)`` and
    ``transpose(jvp(moe_experts))``) is that scope."""
    parts = split_path(op_name)
    phase_at = next(
        (i for i, p in enumerate(parts) if p in STEP_PHASES), None
    )
    if phase_at is None:
        return UNATTRIBUTED, None, ""
    phase = parts[phase_at]
    direction = None
    if phase == "forward_backward":
        direction = BACKWARD if any(
            p.startswith("transpose(") for p in parts
        ) else FORWARD
    module = []
    for p in parts[phase_at + 1:-1]:
        wrapped = _WRAPPED.match(p)
        if wrapped and wrapped.group(1) in MODEL_SCOPES:
            p = wrapped.group(1)
        if ("(" in p or p in _JAX_FRAMES or p in KERNELS
                or p in STEP_PHASES or _BRANCH.match(p)):
            continue
        m = _LAYER.match(p)
        module.append(m.group(1) + "*" if m else p)
    return phase, direction, "/".join(module)


def kernel_of(ins: HloInstruction) -> Optional[str]:
    """The registered kernel a custom-call runs, from its
    ``kernel_metadata``; None for anything else (a ``get-tuple-element``
    of the call's result repeats its attributes and runs nothing)."""
    if ins.opcode != "custom-call":
        return None
    name = dict(ins.kernel_metadata).get(KERNEL_KEY)
    return name if name in KERNELS else None


def tiles_of(kernel_metadata) -> str:
    """``"512x256"`` — ``block_q`` x ``block_k``, then the head's widths
    where q/k and v differ: ``d192/128`` (``d_qk`` / ``d_v``), or
    ``d128+64/128`` where the score is a sum over parts (``d_nope`` +
    ``d_rope`` / ``d_v``: latent attention's kernels on the projections'
    own outputs) — from a kernel's ``kernel_metadata`` (pairs or a dict:
    the flash kernels choose their tiles per call,
    ``ops/attention.py:_flash_tiles``); "" where the kernel names none."""
    meta = dict(kernel_metadata)
    tiles = "x".join(meta[k] for k in ("block_q", "block_k") if k in meta)
    if tiles and "d_rope" in meta:
        tiles += f" d{meta.get('d_nope')}+{meta['d_rope']}/{meta.get('d_v')}"
    elif tiles and meta.get("d_qk") != meta.get("d_v"):
        # q and k wider than v (latent attention): worth seeing beside
        tiles += f" d{meta.get('d_qk')}/{meta.get('d_v')}"
    return tiles


def _scope(ins: HloInstruction, op_name: str, how: str) -> Optional[OpScope]:
    """The scope ``op_name`` gives ``ins``; None when it names no phase."""
    phase, direction, module = classify_path(op_name)
    if phase == UNATTRIBUTED:
        return None
    return OpScope(phase=phase, direction=direction, module=module,
                   kernel=kernel_of(ins), op_name=op_name, how=how)


def scope_map(module_or_text) -> Dict[str, OpScope]:
    """Instruction name -> :class:`OpScope` for every instruction of the
    compiled module (an :class:`HloModule`, or anything
    ``parse_hlo_module`` takes). The rules and their order are the module
    docstring's."""
    module = (
        module_or_text if isinstance(module_or_text, HloModule)
        else parse_hlo_module(module_or_text)
    )
    instructions = module.instructions()
    by_comp: Dict[str, List[HloInstruction]] = collections.defaultdict(list)
    for ins in instructions:
        by_comp[ins.computation].append(ins)

    out: Dict[str, OpScope] = {}
    for ins in instructions:  # own
        scope = _scope(ins, ins.op_name, "own") if ins.op_name else None
        if scope is not None:
            out[ins.name] = scope
    for ins in instructions:  # fused: the majority of what it fused
        if ins.opcode != "fusion":
            continue
        inner = [out[i.name] for c in ins.calls for i in by_comp.get(c, ())
                 if i.name in out]
        if not inner:
            continue
        parts = collections.Counter(s.part for s in inner)
        mine = out.get(ins.name)
        top = parts.most_common(1)[0]
        if mine is None or parts[mine.part] < top[1]:
            path = collections.Counter(
                s.op_name for s in inner if s.part == top[0]
            ).most_common(1)[0][0]
            mine = _scope(ins, path, "fused")
        phases = collections.Counter(s.phase for s in inner)
        out[ins.name] = dataclasses.replace(
            mine, mix=tuple(phases.most_common()) if len(phases) > 1 else ())

    # caller: what runs inside a cond branch, a while body or a fused
    # computation inherits the scope of the instruction that calls it,
    # through nested calls
    caller_of: Dict[str, HloInstruction] = {}
    for ins in instructions:
        for c in ins.calls:
            caller_of.setdefault(c, ins)

    def inherited(comp: str, seen=()) -> Optional[OpScope]:
        caller = caller_of.get(comp)
        if caller is None or comp in seen:
            return None
        return out.get(caller.name) or inherited(
            caller.computation, seen + (comp,)
        )

    # (the text prints a computation before its callers: walk it backwards,
    # so that a caller is placed before what it calls asks for its scope)
    for comp, members in reversed(list(by_comp.items())):
        pending = [i for i in members
                   if i.name not in out and i.opcode not in STRUCTURAL]
        if not pending:
            continue
        above = inherited(comp)
        if above is not None:
            for ins in pending:
                out[ins.name] = dataclasses.replace(
                    above, kernel=kernel_of(ins), how="caller", mix=())
            continue
        # flow: nearest user, else nearest producer, scoped by its own
        # metadata, within the instruction's own computation
        users: Dict[str, List[str]] = collections.defaultdict(list)
        producers = {i.name: i.operands for i in members}
        for ins in members:
            for o in ins.operands:
                users[o].append(ins.name)
        for ins in pending:
            found = (_walk(ins.name, lambda n: users.get(n, ()), out)
                     or _walk(ins.name, lambda n: producers.get(n, ()), out))
            if found is not None:
                out[ins.name] = dataclasses.replace(
                    found, kernel=kernel_of(ins), how="flow", mix=())

    for ins in instructions:
        if ins.name not in out:
            out[ins.name] = OpScope(
                phase=UNATTRIBUTED, direction=None, module="",
                kernel=kernel_of(ins), op_name=ins.op_name, how="none",
            )
    for ins in instructions:
        if out[ins.name].kernel is not None:
            out[ins.name] = dataclasses.replace(
                out[ins.name], tiles=tiles_of(ins.kernel_metadata))
    return out


def _walk(start: str, neighbours, scoped: Dict[str, OpScope],
          limit: int = 64) -> Optional[OpScope]:
    """Breadth-first from ``start`` along ``neighbours`` to the nearest
    instruction ``scoped`` by its own metadata (never through another
    inference), at most ``limit`` instructions away."""
    seen, frontier = {start}, [start]
    while frontier and len(seen) <= limit:
        nxt = []
        for n in frontier:
            for m in neighbours(n):
                if m in seen:
                    continue
                seen.add(m)
                s = scoped.get(m)
                if s is not None and s.how in ("own", "fused"):
                    return s
                nxt.append(m)
        frontier = nxt
    return None
