"""Whose is this device op? The compiled step's text, read into instruction
name -> (phase of the step, model scope, kernel).

A device event of a TPU trace names an HLO instruction (``fusion.895``,
``copy.2098``, ``self_attention.117``) and nothing of the program. The
compiled module knows more: each instruction's ``metadata`` keeps the
``op_name`` path it was traced under,

    jit(train_step)/forward_backward/transpose(jvp(GPTModel))/transformer/
        layer_3/self_attention/mla_project/q_b_proj/dot_general

which holds the step's phase (a ``jax.named_scope`` of the program), JAX's
own ``transpose(`` on every op of the backward pass, and the model's scopes;
a Pallas kernel's custom-call carries the name the program gave it in
``frontend_attributes={kernel_metadata={"kernel": ...}}``.

The benchmark's own copy of the rules of the program's reader
(``apex_tpu/monitor/xray/timeline/hlo_scopes.py`` over ``analysis/hlo/
parser.py``), kept here so that no later PR moves what a phase's time
means. It imports nothing of the program: the NAMES (which path components
are phases, which are model scopes, the key a kernel's name rides under)
are arguments, which the driver takes from the program's registry.

An instruction without metadata of its own (a copy the compiler put in, a
prefetch) is placed in this order, and ``Scope.how`` says which applied:
``own`` (its own ``op_name``); ``fused`` (a fusion belongs where most of
what it fused was traced, a tie going to its own name: XLA fuses the Adam
update and the non-finite check of the new parameters into ONE pass, named
after the check); ``caller`` (an instruction of a called computation, a
``cond`` branch or a ``while`` body, takes the scope of the instruction
that calls it); ``flow`` (the nearest user's scope, else the nearest
producer's); ``none``.
"""

import collections
import re

UNATTRIBUTED = "(unattributed)"
FORWARD, BACKWARD = "forward", "backward"

#: opcodes that occupy no device time of their own and take no scope
STRUCTURAL = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id",
})

Instruction = collections.namedtuple(
    "Instruction", "name opcode op_name operands calls kernel_metadata "
    "computation")
#: ``part`` is the phase, split by direction where it has one (the phase
#: ``forward_backward`` reads ``forward`` or ``backward``); ``scope`` the
#: innermost model scope of the path, "" where none; ``kernel`` the name in
#: the custom-call's ``kernel_metadata``, None where none
Scope = collections.namedtuple("Scope", "part scope kernel op_name how")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.+)$")
_COMPUTATION = re.compile(
    r"^(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$")
_OPCODE = re.compile(r"([a-z][\w\-]*)\(")
_DTYPES = frozenset({
    "pred", "s4", "u4", "s8", "u8", "s16", "u16", "s32", "u32", "s64", "u64",
    "f8e4m3fn", "f8e5m2", "f8e4m3b11fnuz", "f8e4m3fnuz", "f8e5m2fnuz",
    "bf16", "f16", "f32", "f64", "c64", "c128"})
_NAME = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{")
# not the tail of ``kernel_metadata={``
_METADATA = re.compile(r"(?<!\w)metadata=\{")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_KERNEL_METADATA = re.compile(r"kernel_metadata=\{")
_JSON_PAIR = re.compile(r'"((?:[^"\\]|\\.)*)"\s*:\s*"((?:[^"\\]|\\.)*)"')


def balanced(text, start, open_ch="{", close_ch="}"):
    """``(body, index of the closer)`` of the bracketed section that opens
    at ``text[start]``, nesting-safe; a double-quoted string is opaque (an
    ``op_name`` may hold a bracket). Raises ValueError on a section that
    never closes (an event's name cut short)."""
    marks = re.compile('["' + re.escape(open_ch) + re.escape(close_ch) + "]")
    depth, pos = 0, start
    while True:
        m = marks.search(text, pos)
        if m is None:
            raise ValueError(f"unbalanced {open_ch!r} at index {start}")
        i, c = m.start(), m.group()
        pos = i + 1
        if c == '"':
            while True:
                k = text.find('"', pos)
                if k < 0:
                    raise ValueError(f"unbalanced {open_ch!r} at {start}")
                pos = k + 1
                escapes = 0
                while text[k - 1 - escapes] == "\\":
                    escapes += 1
                if escapes % 2 == 0:
                    break
        elif c == open_ch:
            depth += 1
        else:
            depth -= 1
            if depth == 0:
                return text[start + 1:i], i


def kernel_metadata(attrs):
    """The pairs of ``kernel_metadata={...}`` in an instruction's text (a
    line of a module, or a trace event's name, where it prints as
    multi-line JSON) as a dict; empty where the text has none."""
    m = _KERNEL_METADATA.search(attrs)
    if m is None:
        return {}
    try:
        body, _ = balanced(attrs, m.end() - 1)
    except ValueError:
        body = attrs[m.end():]
    return dict(_JSON_PAIR.findall(body))


def parse_instruction(text, computation=""):
    """One instruction's text as an :class:`Instruction`; None when
    ``text`` is none."""
    m = _INSTR.match(" ".join(text.split("\n")))
    if m is None:
        return None
    rest = m.group("rest")
    opcode, paren = "", -1
    for om in _OPCODE.finditer(rest):
        if om.group(1) not in _DTYPES:
            opcode, paren = om.group(1), om.end() - 1
            break
    operands, attrs = (), rest
    if paren >= 0:
        try:
            operand_text, end = balanced(rest, paren, "(", ")")
        except ValueError:
            operand_text, end = rest[paren + 1:], len(rest)
        operands = tuple(_NAME.findall(operand_text))
        attrs = rest[end + 1:]
    calls = _CALLS.findall(attrs)
    bm = _BRANCHES.search(attrs)
    if bm:
        calls += _NAME.findall(balanced(attrs, bm.end() - 1)[0])
    op_name = ""
    mm = _METADATA.search(attrs)
    if mm:
        om = _OP_NAME.search(balanced(attrs, mm.end() - 1)[0])
        op_name = om.group(1) if om else ""
    return Instruction(m.group("name"), opcode, op_name, operands,
                       tuple(calls), kernel_metadata(attrs), computation)


def instructions(text):
    """Every instruction of every computation of a module's text, in text
    order. A computation opens with ``%name (...) ... {`` or ``ENTRY ... {``
    at column 0 and closes with ``}`` alone; an instruction may span lines
    (``kernel_metadata={`` prints as multi-line JSON)."""
    out, comp, pending = [], "", None

    def flush():
        nonlocal pending
        if pending is not None:
            ins = parse_instruction(pending, comp)
            if ins is not None:
                out.append(ins)
        pending = None

    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            m = _COMPUTATION.match(line)
            if m:
                flush()
                comp = m.group("name")
                continue
        if line.rstrip() == "}":
            flush()
            comp = ""
            continue
        if not comp:
            continue
        if _INSTR.match(line):
            flush()
            pending = line
        elif pending is not None:
            pending += " " + line.strip()
    flush()
    return out


def split_path(op_name):
    """``op_name`` cut at the ``/`` outside parentheses. XLA joins the
    paths of ops it merged with ``;``: the first speaks for all."""
    parts, depth, cur = [], 0, []
    for ch in op_name.split(";", 1)[0]:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    parts.append("".join(cur))
    return [p for p in parts if p]


#: a scope opened while a transform traced it: ``transpose(jvp(moe_experts))``
_WRAPPED = re.compile(r"^(?:\w+\()+(\w+)\)+$")


def classify_path(op_name, phases, scopes, split="forward_backward"):
    """``(part, scope)`` of one ``op_name`` path. The phase is the first
    component that is one of ``phases``; the phase ``split`` reads
    ``backward`` when any component is a ``transpose(...)`` (JAX's mark on
    the ops of a transposed jvp), else ``forward``. The scope is the LAST
    component after the phase that is one of ``scopes``, bare or wrapped in
    transforms (``transpose(jvp(moe_experts))``: a ``custom_vjp`` rule that
    differentiates its own forward)."""
    parts = split_path(op_name)
    at = next((i for i, p in enumerate(parts) if p in phases), None)
    if at is None:
        return UNATTRIBUTED, ""
    part = parts[at]
    if part == split:
        part = BACKWARD if any(
            p.startswith("transpose(") for p in parts) else FORWARD
    scope = ""
    for p in parts[at + 1:]:
        w = _WRAPPED.match(p)
        p = w.group(1) if w else p
        if p in scopes:
            scope = p
    return part, scope


def scope_map(text, phases, scopes, kernel_key="kernel"):
    """Instruction name -> :class:`Scope` for every instruction of the
    compiled module's ``text``, by the module docstring's rules."""
    phases, scopes = frozenset(phases), frozenset(scopes)
    every = instructions(text)
    by_comp = collections.defaultdict(list)
    for ins in every:
        by_comp[ins.computation].append(ins)

    def kernel_of(ins):
        if ins.opcode != "custom-call":
            return None  # a get-tuple-element repeats the attributes
        return ins.kernel_metadata.get(kernel_key)

    def scope_of(ins, op_name, how):
        part, scope = classify_path(op_name, phases, scopes)
        if part == UNATTRIBUTED:
            return None
        return Scope(part, scope, kernel_of(ins), op_name, how)

    out = {}
    for ins in every:  # own
        s = scope_of(ins, ins.op_name, "own") if ins.op_name else None
        if s is not None:
            out[ins.name] = s
    for ins in every:  # fused: the majority of what it fused
        if ins.opcode != "fusion":
            continue
        inner = [out[i.name] for c in ins.calls for i in by_comp.get(c, ())
                 if i.name in out]
        if not inner:
            continue
        parts = collections.Counter(s.part for s in inner)
        mine, top = out.get(ins.name), parts.most_common(1)[0]
        if mine is None or parts[mine.part] < top[1]:
            path = collections.Counter(
                s.op_name for s in inner if s.part == top[0]
            ).most_common(1)[0][0]
            out[ins.name] = scope_of(ins, path, "fused")

    caller_of = {}
    for ins in every:
        for c in ins.calls:
            caller_of.setdefault(c, ins)

    def inherited(comp, seen=()):
        caller = caller_of.get(comp)
        if caller is None or comp in seen:
            return None
        return out.get(caller.name) or inherited(
            caller.computation, seen + (comp,))

    # the text prints a computation before its callers: walk it backwards,
    # so that a caller is placed before what it calls asks for its scope
    for comp, members in reversed(list(by_comp.items())):
        pending = [i for i in members
                   if i.name not in out and i.opcode not in STRUCTURAL]
        if not pending:
            continue
        above = inherited(comp)
        if above is not None:
            for ins in pending:
                out[ins.name] = above._replace(
                    kernel=kernel_of(ins), how="caller")
            continue
        users = collections.defaultdict(list)
        producers = {i.name: i.operands for i in members}
        for ins in members:
            for o in ins.operands:
                users[o].append(ins.name)
        for ins in pending:
            found = (_walk(ins.name, lambda n: users.get(n, ()), out)
                     or _walk(ins.name, lambda n: producers.get(n, ()), out))
            if found is not None:
                out[ins.name] = found._replace(
                    kernel=kernel_of(ins), how="flow")
    for ins in every:
        if ins.name not in out:
            out[ins.name] = Scope(UNATTRIBUTED, "", kernel_of(ins),
                                  ins.op_name, "none")
    return out


def _walk(start, neighbours, scoped, limit=64):
    """Breadth-first from ``start`` to the nearest instruction scoped by
    its own metadata (never through another inference), at most ``limit``
    instructions away."""
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
