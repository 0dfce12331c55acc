"""The repo's single HLO/MLIR text scraper: brace-aware, nesting-safe.

XLA's optimized HLO text is the ground truth of what the compiler
actually emitted — realized donation aliases (donation.py), the real
collective inventory (comms_diff.py), entry parameter/output shardings
(sharding_audit.py). Scraping it with ad-hoc regexes scattered across
passes rots fast (the old ``donation._realized_aliases`` matched the
first ``}`` it saw), so ALL ``.as_text()`` parsing lives here and the
``lint.hlo-text`` rule forbids it anywhere else; callers hand this
module the ``Lowered``/``Compiled`` object (or its text) and get
structured records back.

What the parser understands, and deliberately nothing more:

- module header: ``input_output_alias={...}`` (nesting-safe),
- computations: ``%name (...) -> ... {`` / ``ENTRY %name ... {`` blocks,
  so a collective inside a while-loop body is still found (it appears
  once in text however many times the loop runs — callers own that
  caveat),
- collective instructions (``all-reduce`` / ``all-gather`` /
  ``reduce-scatter`` / ``collective-permute`` / ``all-to-all``, sync or
  ``-start`` async forms; ``-done`` halves are skipped) with operand
  shapes/dtypes, ``replica_groups`` (literal ``{{0,1},{2,3}}`` or iota
  ``[2,2]<=[4]`` form; collective-permute prints
  ``source_target_pairs={{src,dst},...}`` instead and is captured as
  such), ``channel_id``, and the ``metadata={op_name=...
  source_file=... source_line=N}`` provenance XLA carries through,
- entry parameters and the entry ROOT with their ``sharding={...}``
  annotations and jax's human labels (``params['params'][...]``),
- any single instruction (:func:`parse_instruction`, and all of a
  module's through ``HloModule.instructions()``): name, opcode, operand
  and called-computation names, the ``op_name`` scope path, a
  custom-call's target and its ``kernel_metadata``. An instruction may
  span lines (a Pallas kernel's ``kernel_metadata={...}`` prints as
  multi-line JSON); the same function reads a TPU profiler event's
  name, which is the instruction's whole text
  (``monitor/xray/timeline``).

Byte conventions match the xray ledger's (the differ depends on it):
a collective's payload is its OPERAND — for all-gather the local shard,
for reduce-scatter the full pre-scatter array. Element counts, not
bytes, are the cross-checking currency: backends legalize dtypes (CPU
XLA widens bf16 collectives to f32) without changing element counts.
"""

import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

__all__ = [
    "HloShape",
    "HloSharding",
    "HloOperand",
    "HloCollective",
    "HloParam",
    "HloInstruction",
    "HloModule",
    "COLLECTIVE_KINDS",
    "module_text",
    "parse_hlo_module",
    "parse_instruction",
    "balanced",
    "parse_iota_list",
    "realized_aliases",
    "mlir_main_signature",
    "mlir_marked_aliases",
]

#: HLO collective opcodes the parser extracts (the sync spellings; the
#: async ``-start`` forms normalize onto these and ``-done`` is skipped)
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "bf16": 2,
    "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}


def module_text(obj) -> str:
    """The HLO/MLIR text of ``obj``: a ``jax.stages.Lowered`` /
    ``Compiled`` (or anything with ``.as_text()``), or a plain string
    passed through. The ONE place ``.as_text`` is called (lint.hlo-text
    pins that)."""
    if isinstance(obj, str):
        return obj
    if not hasattr(obj, "as_text"):
        raise TypeError(
            f"expected HLO text or an object with .as_text(), got "
            f"{type(obj).__name__}"
        )
    return obj.as_text()


def balanced(text: str, start: int, open_ch: str = "{",
             close_ch: str = "}") -> Tuple[str, int]:
    """The contents of the bracketed section whose opener is at
    ``text[start]``, nesting-safe. Returns ``(body, end_index)`` where
    ``end_index`` points at the closer; raises on malformed input.
    Double-quoted strings are opaque: a bracket inside a quoted
    metadata value (e.g. an ``op_name`` from a user ``named_scope``
    containing ``{``, carried verbatim by XLA) neither opens nor
    closes anything."""
    if start >= len(text) or text[start] != open_ch:
        raise ValueError(
            f"expected {open_ch!r} at index {start}, found "
            f"{text[start:start + 1]!r}"
        )
    # hop from bracket to bracket (and quote to quote) instead of walking
    # every character: an instruction's text runs to kilobytes and a
    # profiler capture holds tens of thousands of them
    marks = re.compile('["' + re.escape(open_ch) + re.escape(close_ch) + "]")
    depth, pos = 0, start
    while True:
        m = marks.search(text, pos)
        if m is None:
            break
        i, c = m.start(), m.group()
        pos = i + 1
        if c == '"':
            while True:
                k = text.find('"', pos)
                if k < 0:
                    raise ValueError(
                        f"unbalanced {open_ch!r} section at index {start}"
                    )
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
    raise ValueError(f"unbalanced {open_ch!r} section at index {start}")


def parse_iota_list(dims: Sequence[int], reshape: Sequence[int],
                    transpose: Optional[Sequence[int]] = None) -> List[List[int]]:
    """Expand XLA's iota shorthand ``[dims]<=[reshape]`` (optionally
    ``T(transpose)``): ``iota(prod(reshape)).reshape(reshape)
    .transpose(t).reshape(dims)``, returned as ``dims[0]`` rows of
    ``prod(dims[1:])`` ids each — for ``replica_groups=[G,S]<=[...]``
    that is G groups of S devices."""
    import numpy as np

    n = int(np.prod(reshape, dtype=np.int64))
    arr = np.arange(n).reshape(tuple(reshape))
    if transpose is not None:
        arr = arr.transpose(tuple(transpose))
    arr = arr.reshape(tuple(dims))
    if arr.ndim == 1:
        return [arr.tolist()]
    return arr.reshape(dims[0], -1).tolist()


@dataclasses.dataclass(frozen=True)
class HloShape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def elements(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def nbytes(self) -> int:
        return self.elements * _DTYPE_BYTES.get(self.dtype, 4)

    def __str__(self) -> str:
        return f"{self.dtype}[{','.join(str(d) for d in self.dims)}]"


_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([\d,]*)\]")


def _parse_shapes(text: str) -> List[HloShape]:
    out = []
    for m in _SHAPE_RE.finditer(text):
        dtype = m.group(1)
        if dtype not in _DTYPE_BYTES and dtype != "token":
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        out.append(HloShape(dtype, dims))
    return out


@dataclasses.dataclass(frozen=True)
class HloSharding:
    """One ``sharding={...}`` annotation, as much as the auditors need:
    is the value fully replicated, and over how many tile dims is it
    actually split."""

    raw: str
    replicated: bool = False
    maximal: bool = False
    tile_dims: Tuple[int, ...] = ()
    last_tile_dim_replicate: bool = False

    @property
    def fully_replicated(self) -> bool:
        """True when every device holds the whole value: ``replicated``,
        or a ``devices=[...]`` assignment whose every data tile dim is 1
        (all the fan-out sits in a trailing replicate dim)."""
        if self.replicated:
            return True
        if self.maximal or not self.tile_dims:
            return False
        data_dims = (
            self.tile_dims[:-1] if self.last_tile_dim_replicate
            else self.tile_dims
        )
        return all(d == 1 for d in data_dims)


_TILE_RE = re.compile(r"devices=\[([\d,]+)\]")


def parse_sharding(raw: str) -> HloSharding:
    raw = raw.strip()
    if raw == "replicated":
        return HloSharding(raw=raw, replicated=True)
    if raw.startswith("maximal"):
        return HloSharding(raw=raw, maximal=True)
    m = _TILE_RE.search(raw)
    dims = tuple(int(d) for d in m.group(1).split(",")) if m else ()
    return HloSharding(
        raw=raw, tile_dims=dims,
        last_tile_dim_replicate="last_tile_dim_replicate" in raw,
    )


@dataclasses.dataclass(frozen=True)
class HloOperand:
    shape: HloShape

    @property
    def elements(self) -> int:
        return self.shape.elements

    @property
    def nbytes(self) -> int:
        return self.shape.nbytes


@dataclasses.dataclass(frozen=True)
class HloCollective:
    """One collective instruction of the module (any computation).

    ``replica_groups`` is how every collective EXCEPT collective-permute
    spells its participants; permutes instead print
    ``source_target_pairs={{src,dst},...}`` (captured in
    ``source_target_pairs``, with ``replica_groups`` left empty).

    ``is_async`` marks the ``-start`` spelling: XLA split the op into a
    ``-start``/``-done`` pair, i.e. the scheduler may overlap its wire
    time with compute between the halves — the emitted-HLO evidence the
    overlap-aware schedules' proof loop reads (the timeline analyzer
    fuses the same pairs into in-flight intervals on the measured
    side)."""

    kind: str  # one of COLLECTIVE_KINDS
    name: str  # %all-reduce.50
    computation: str
    result: HloShape
    operands: Tuple[HloOperand, ...]
    replica_groups: Tuple[Tuple[int, ...], ...]  # () == one group of all
    channel_id: Optional[int]
    op_name: str
    source_file: str
    source_line: int
    line: int  # 1-based line in the module text
    source_target_pairs: Tuple[Tuple[int, int], ...] = ()
    is_async: bool = False  # emitted as a -start/-done pair

    @property
    def group_size(self) -> int:
        """Devices per replica group (0 when the groups are implicit
        'everyone' — the caller supplies the device count)."""
        return len(self.replica_groups[0]) if self.replica_groups else 0

    @property
    def elements(self) -> int:
        """Total operand elements — the ledger-convention payload."""
        return sum(op.elements for op in self.operands)

    @property
    def nbytes(self) -> int:
        return sum(op.nbytes for op in self.operands)


@dataclasses.dataclass(frozen=True)
class HloParam:
    """One entry-computation parameter."""

    index: int  # parameter(N) — the flat input-leaf position
    name: str  # %param.12
    shape: HloShape
    sharding: Optional[HloSharding]
    label: str  # jax's op_name metadata: params['params'][...]
    line: int

    @property
    def nbytes(self) -> int:
        return self.shape.nbytes


@dataclasses.dataclass(frozen=True)
class HloInstruction:
    """One instruction, as far as the scope join reads it
    (``monitor/xray/timeline/hlo_scopes.py``)."""

    name: str  # no leading %: the key a profiler event joins on
    opcode: str
    op_name: str  # metadata's scope path; "" when XLA kept none
    operands: Tuple[str, ...]  # operand instruction names
    #: computations it runs: a fusion's / reduce's (``calls=``,
    #: ``to_apply=``), a conditional's branches, a while's body and
    #: condition
    calls: Tuple[str, ...]
    custom_call_target: str = ""
    #: a Pallas kernel's ``frontend_attributes={kernel_metadata={...}}``
    kernel_metadata: Tuple[Tuple[str, str], ...] = ()
    computation: str = ""
    line: int = 0


@dataclasses.dataclass
class HloModule:
    """The parsed module: what the HLO passes read."""

    name: str
    collectives: List[HloCollective]
    entry_params: List[HloParam]
    entry_root_shapes: List[HloShape]
    entry_root_shardings: Optional[List[HloSharding]]
    input_output_alias: Dict[int, int]  # param index -> output index
    entry_name: str = ""
    text: str = dataclasses.field(default="", repr=False)
    _instructions: Optional[List[HloInstruction]] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def collectives_in_entry(self) -> List[HloCollective]:
        return [c for c in self.collectives if c.computation == self.entry_name]

    def instructions(self) -> List[HloInstruction]:
        """Every instruction of every computation, in text order. Parsed
        on first use and kept: the passes that read collectives alone
        never pay for it."""
        if self._instructions is None:
            parsed = (
                parse_instruction(text, comp, lineno)
                for comp, _, lineno, text in _iter_instructions(self.text)
            )
            self._instructions = [ins for ins in parsed if ins is not None]
        return self._instructions


_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{")
_PAIRS_RE = re.compile(r"source_target_pairs=\{")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?"
)
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
# not the tail of ``kernel_metadata={``
_METADATA_RE = re.compile(r"(?<!\w)metadata=\{")
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_SOURCE_FILE_RE = re.compile(r'source_file="((?:[^"\\]|\\.)*)"')
_SOURCE_LINE_RE = re.compile(r"source_line=(\d+)")
_STACK_FRAME_RE = re.compile(r"stack_frame_id=(\d+)")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_SHARDING_RE = re.compile(r"sharding=\{")

#: instruction opener: ``  %name = type opcode(``  (ROOT optional)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.+)$"
)
_COMPUTATION_RE = re.compile(
    r"^(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$"
)
_PARAM_RE = re.compile(
    r"^\s*%(?P<name>[\w.\-]+)\s*=\s*(?P<type>\S+)\s+parameter\((?P<idx>\d+)\)"
)


def _parse_replica_groups(attrs: str) -> Tuple[Tuple[int, ...], ...]:
    m = _GROUPS_IOTA_RE.search(attrs)
    if m:
        dims = [int(d) for d in m.group(1).split(",")]
        reshape = [int(d) for d in m.group(2).split(",")]
        transpose = (
            [int(d) for d in m.group(3).split(",")] if m.group(3) else None
        )
        return tuple(
            tuple(g) for g in parse_iota_list(dims, reshape, transpose)
        )
    m = _GROUPS_LITERAL_RE.search(attrs)
    if m is None:
        return ()
    body, _ = balanced(attrs, m.end() - 1)
    groups = []
    for gm in re.finditer(r"\{([\d,\s]*)\}", body):
        ids = tuple(int(x) for x in gm.group(1).split(",") if x.strip())
        groups.append(ids)
    return tuple(groups)


def _parse_source_target_pairs(attrs: str) -> Tuple[Tuple[int, int], ...]:
    """collective-permute's ``source_target_pairs={{src,dst},...}``."""
    m = _PAIRS_RE.search(attrs)
    if m is None:
        return ()
    body, _ = balanced(attrs, m.end() - 1)
    pairs = []
    for gm in re.finditer(r"\{(\d+)\s*,\s*(\d+)\}", body):
        pairs.append((int(gm.group(1)), int(gm.group(2))))
    return tuple(pairs)


def _parse_stack_frames(text: str) -> Dict[int, Tuple[str, int]]:
    """``{stack_frame_id: (file, line)}`` from the module header. Current
    XLA prints source locations once, as ``FileNames`` / ``FileLocations``
    / ``StackFrames`` index tables ahead of the computations, and each
    instruction's metadata carries only ``stack_frame_id=N``. Empty when
    the text has inline ``source_file=``/``source_line=`` instead."""
    tables: Dict[str, Dict[int, str]] = {}
    section = None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            break  # header is over at the first computation
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = tables.setdefault(line, {})
            continue
        idx, _, body = line.partition(" ")
        if section is not None and idx.isdigit():
            section[int(idx)] = body
    files = {
        i: b.strip().strip('"') for i, b in tables.get("FileNames", {}).items()
    }
    locations = {}
    for i, b in tables.get("FileLocations", {}).items():
        fm = re.search(r"file_name_id=(\d+)", b)
        lm = re.search(r"\bline=(\d+)", b)
        if fm and lm:
            locations[i] = (files.get(int(fm.group(1)), ""), int(lm.group(1)))
    frames = {}
    for i, b in tables.get("StackFrames", {}).items():
        m = re.search(r"file_location_id=(\d+)", b)
        if m and int(m.group(1)) in locations:
            frames[i] = locations[int(m.group(1))]
    return frames


def _parse_metadata(
    attrs: str, frames: Optional[Dict[int, Tuple[str, int]]] = None,
) -> Tuple[str, str, int]:
    m = _METADATA_RE.search(attrs)
    if m is None:
        return "", "", 0
    body, _ = balanced(attrs, m.end() - 1)
    op = _OP_NAME_RE.search(body)
    sf = _SOURCE_FILE_RE.search(body)
    sl = _SOURCE_LINE_RE.search(body)
    source = (sf.group(1) if sf else "", int(sl.group(1)) if sl else 0)
    frame = _STACK_FRAME_RE.search(body)
    if not sf and frame and frames:
        source = frames.get(int(frame.group(1)), source)
    return (op.group(1) if op else "",) + source


def _parse_sharding_attr(attrs: str) -> Optional[HloSharding]:
    m = _SHARDING_RE.search(attrs)
    if m is None:
        return None
    body, _ = balanced(attrs, m.end() - 1)
    return parse_sharding(body)


def _parse_tuple_shardings(attrs: str) -> Optional[List[HloSharding]]:
    """``sharding={{...}, {...}}`` on a tuple-shaped ROOT, or a single
    sharding applied to every leaf."""
    m = _SHARDING_RE.search(attrs)
    if m is None:
        return None
    body, _ = balanced(attrs, m.end() - 1)
    body = body.strip()
    if not body.startswith("{"):
        return [parse_sharding(body)]
    out, i = [], 0
    while i < len(body):
        if body[i] == "{":
            inner, end = balanced(body, i)
            out.append(parse_sharding(inner))
            i = end + 1
        else:
            i += 1
    return out


def realized_aliases(compiled_or_text) -> Dict[int, int]:
    """``{param_index: output_index}`` from the optimized HLO module's
    ``input_output_alias`` header (absent section = nothing realized).
    Nesting-safe: the section is extracted by brace matching, not
    first-``}``-wins."""
    text = module_text(compiled_or_text)
    m = re.search(r"input_output_alias=\{", text)
    if m is None:
        return {}
    section, _ = balanced(text, m.end() - 1)
    realized: Dict[int, int] = {}
    for mm in re.finditer(r"\{([\d ,]*)\}:\s*\((\d+)", section):
        out_idx = int(mm.group(1).split(",")[0]) if mm.group(1).strip() else 0
        realized[int(mm.group(2))] = out_idx
    return realized


def mlir_main_signature(lowered_or_text) -> Optional[str]:
    """The argument list of the lowered MLIR's public ``@main`` func, by
    paren matching (None when there is no such func)."""
    text = module_text(lowered_or_text)
    m = re.search(r"func\.func\s+public\s+@main\s*\(", text)
    if m is None:
        return None
    try:
        body, _ = balanced(text, m.end() - 1, "(", ")")
    except ValueError:
        return None
    return body


def mlir_marked_aliases(
    lowered_or_text,
) -> Tuple[Optional[Dict[int, Optional[int]]], int]:
    """``{param_index: output_index_or_None}`` for parameters jax marked
    donated in the lowered MLIR, plus the entry parameter count. jax
    spells the mark two ways: ``tf.aliasing_output = N`` when it matched
    the donated input to output N itself, or ``jax.buffer_donor = true``
    when it hands XLA the buffer and lets the compiler pick the alias
    (value None). ``(None, 0)`` when the signature cannot be found."""
    sig = mlir_main_signature(lowered_or_text)
    if sig is None:
        return None, 0
    marked: Dict[int, Optional[int]] = {}
    chunks = re.split(r"%arg(\d+)\s*:", sig)
    # chunks: [prefix, idx0, body0, idx1, body1, ...]
    nparams = 0
    for i in range(1, len(chunks) - 1, 2):
        param = int(chunks[i])
        nparams = max(nparams, param + 1)
        m = re.search(r"tf\.aliasing_output\s*=\s*(\d+)", chunks[i + 1])
        if m:
            marked[param] = int(m.group(1))
        elif re.search(r"jax\.buffer_donor\s*=\s*true", chunks[i + 1]):
            marked[param] = None
    return marked, nparams


def _iter_instructions(text: str) -> Iterator[Tuple[str, bool, int, str]]:
    """``(computation_name, in_entry, line_number, instruction_text)``
    tuples. Computation bodies open with ``%name (...) ... {`` or
    ``ENTRY ... {`` at column 0 and close with a line that is ``}`` alone.
    An instruction that spans lines (a custom-call whose
    ``kernel_metadata={`` prints as multi-line JSON, continued by a line
    that STARTS with ``}}``) is yielded as one line, its continuations
    joined by spaces."""
    comp, in_entry = "", False
    pending: Optional[list] = None
    for lineno, line in enumerate(text.splitlines(), start=1):
        if line.startswith(("%", "ENTRY")):
            m = _COMPUTATION_RE.match(line)
            if m:
                if pending:
                    yield tuple(pending)
                    pending = None
                comp, in_entry = m.group("name"), bool(m.group("entry"))
                continue
        if line.rstrip() == "}":
            if pending:
                yield tuple(pending)
                pending = None
            comp, in_entry = "", False
            continue
        if not comp:
            continue
        if _INSTR_RE.match(line):
            if pending:
                yield tuple(pending)
            pending = [comp, in_entry, lineno, line]
        elif pending:
            pending[3] += " " + line.strip()
    if pending:
        yield tuple(pending)


#: opcode right before its operand parens: ``<type> opcode(`` — the type
#: may itself be a parenthesized tuple, so scan for the LAST name token
#: preceding a ``(`` from the front of the instruction body
_OPCODE_RE = re.compile(r"([a-z][\w\-]*)\(")


def _find_opcode(rest: str) -> Tuple[str, int]:
    """``(opcode, paren_index)`` of the instruction body after ``= ``.
    The opcode is the first bare identifier directly attached to a
    ``(``; result-type prefixes (shapes like ``f32[8,16]{1,0}`` or
    tuples of them) never put an identifier directly against a paren,
    so the only guard needed is against a bare dtype token."""
    for m in _OPCODE_RE.finditer(rest):
        tok = m.group(1)
        if tok in _DTYPE_BYTES:
            continue
        return tok, m.end() - 1
    return "", -1


_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_KERNEL_METADATA_RE = re.compile(r"kernel_metadata=\{")
_JSON_PAIR_RE = re.compile(r'"((?:[^"\\]|\\.)*)"\s*:\s*"((?:[^"\\]|\\.)*)"')
_CALLS_RE = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)"
)
_BRANCHES_RE = re.compile(r"branch_computations=\{")


def parse_instruction(text: str, computation: str = "",
                      line: int = 0) -> Optional[HloInstruction]:
    """One instruction's text (``%name = type opcode(operands), attrs``,
    with or without ``ROOT``, on one line or several) as an
    :class:`HloInstruction`; None when ``text`` is no instruction. Reads
    a line of a module and a TPU profiler event's name alike."""
    m = _INSTR_RE.match(" ".join(text.split("\n")))
    if m is None:
        return None
    rest = m.group("rest")
    opcode, paren = _find_opcode(rest)
    operands: Tuple[str, ...] = ()
    attrs = rest
    if paren >= 0:
        try:
            operand_text, end = balanced(rest, paren, "(", ")")
        except ValueError:  # an event name cut short by the exporter
            operand_text, end = rest[paren + 1:], len(rest)
        operands = tuple(_OPERAND_NAME_RE.findall(operand_text))
        attrs = rest[end + 1:]
    calls = _CALLS_RE.findall(attrs)
    bm = _BRANCHES_RE.search(attrs)
    if bm:
        body, _ = balanced(attrs, bm.end() - 1)
        calls += _OPERAND_NAME_RE.findall(body)
    target = _TARGET_RE.search(attrs)
    kernel: Tuple[Tuple[str, str], ...] = ()
    km = _KERNEL_METADATA_RE.search(attrs)
    if km:
        try:
            body, _ = balanced(attrs, km.end() - 1)
        except ValueError:
            body = attrs[km.end():]
        kernel = tuple(_JSON_PAIR_RE.findall(body))
    return HloInstruction(
        name=m.group("name"),
        opcode=opcode,
        op_name=_parse_metadata(attrs)[0],
        operands=operands,
        calls=tuple(calls),
        custom_call_target=target.group(1) if target else "",
        kernel_metadata=kernel,
        computation=computation,
        line=line,
    )


def parse_hlo_module(compiled_or_text) -> HloModule:
    """Parse one HLO module's text into the structured form above."""
    text = module_text(compiled_or_text)
    name_m = re.search(r"HloModule\s+([\w.\-]+)", text)
    module = HloModule(
        name=name_m.group(1) if name_m else "",
        collectives=[],
        entry_params=[],
        entry_root_shapes=[],
        entry_root_shardings=None,
        input_output_alias=realized_aliases(text),
        text=text,
    )
    frames = _parse_stack_frames(text)
    # result shapes by instruction name: current XLA prints operands as
    # bare ``%name`` references, so a collective's operand shapes are the
    # result shapes of the instructions it names
    results: Dict[str, List[HloShape]] = {}
    for comp, in_entry, lineno, instr in _iter_instructions(text):
        if in_entry:
            module.entry_name = comp
        m = _INSTR_RE.match(instr)
        if m is None:
            continue
        rest = m.group("rest")
        opcode, paren = _find_opcode(rest)
        result_shapes = _parse_shapes(rest[:paren]) if paren >= 0 else []
        results[m.group("name")] = result_shapes
        if in_entry:
            pm = _PARAM_RE.match(instr)
            if pm:
                shapes = _parse_shapes(pm.group("type"))
                module.entry_params.append(HloParam(
                    index=int(pm.group("idx")),
                    name=f"%{pm.group('name')}",
                    shape=shapes[0] if shapes else HloShape("f32", ()),
                    sharding=_parse_sharding_attr(instr),
                    label=_parse_metadata(instr)[0],
                    line=lineno,
                ))
                continue
            if instr.lstrip().startswith("ROOT "):
                # the result type between `= ` and the opcode's paren
                module.entry_root_shapes = _parse_shapes(rest[:paren])
                module.entry_root_shardings = _parse_tuple_shardings(instr)
        kind = opcode
        if kind.endswith("-done"):
            continue
        is_async = kind.endswith("-start")
        if is_async:
            kind = kind[: -len("-start")]
        if kind not in COLLECTIVE_KINDS:
            continue
        operand_text, end = balanced(rest, paren, "(", ")")
        attrs = rest[end + 1:]
        op_name, source_file, source_line = _parse_metadata(attrs, frames)
        operand_shapes = _parse_shapes(operand_text) or [
            s for ref in _OPERAND_NAME_RE.findall(operand_text)
            for s in results.get(ref, ())
        ]
        module.collectives.append(HloCollective(
            kind=kind,
            name=f"%{m.group('name')}",
            computation=comp,
            result=result_shapes[0] if result_shapes else HloShape("f32", ()),
            operands=tuple(HloOperand(s) for s in operand_shapes),
            replica_groups=_parse_replica_groups(attrs),
            source_target_pairs=_parse_source_target_pairs(attrs),
            channel_id=(
                int(_CHANNEL_RE.search(attrs).group(1))
                if _CHANNEL_RE.search(attrs) else None
            ),
            op_name=op_name,
            source_file=source_file,
            source_line=source_line,
            line=lineno,
            is_async=is_async,
        ))
    return module
