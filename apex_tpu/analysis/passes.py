"""Jaxpr-pass framework: trace a step function, walk it, audit it.

The trace-time half of ``apex_tpu.analysis``. A *pass* receives a
:class:`StepContext` — the closed jaxpr of a step function obtained via
``jax.make_jaxpr`` (abstract tracing: CPU-safe, no execution, args may be
``ShapeDtypeStruct``) plus the ambient mesh and donation intent — and
yields :class:`~apex_tpu.analysis.findings.Finding` records. Passes
register into :data:`JAXPR_PASSES` with :func:`jaxpr_pass`, the same
shape as the AST rule registry in ``lint.py``:

    @jaxpr_pass("precision")
    def precision_pass(ctx):
        for eqn in ctx.iter_eqns():
            ...
            yield Finding(rule="precision.promotion", ...)

Walking covers the WHOLE program: :func:`iter_eqns` recurses into every
sub-jaxpr an equation carries (pjit/shard_map bodies, scan/while bodies,
cond branches, custom_vjp fwd/bwd, remat) — a promotion inside a
rematerialized scan body two levels down is still found. Sites resolve
through the equation's source-info traceback to the first frame that is
neither jax-internal nor one of our thin wrapper modules (the xray
ledger, pipeline p2p), so a flagged collective points at the schedule
that issued it, not at the wrapper that recorded it.

Run everything over a :class:`StepTarget` with :func:`run_passes`; the
CLI (``python -m apex_tpu.analysis``) does exactly that for the in-repo
GPT/BERT step builders (``targets.py``).
"""

import dataclasses
import os
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.analysis.findings import Allowlist, Finding, merge_findings

__all__ = [
    "JAXPR_PASSES",
    "jaxpr_pass",
    "StepContext",
    "StepTarget",
    "iter_eqns",
    "eqn_site",
    "lower_step",
    "run_passes",
]

#: registered jaxpr passes, name -> pass fn(StepContext) -> Iterable[Finding]
JAXPR_PASSES: Dict[str, Callable] = {}

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: wrapper modules whose frames are NOT the interesting call site: the
#: instrumented collective wrappers and the p2p edge helpers — findings
#: should name the schedule/layer that called them. Installed libraries
#: (flax's dtype promotion helpers sit innermost under every nn.Dense)
#: are wrappers in the same sense: the finding belongs to the repo line
#: that called them, which is also what the allowlist matches on
_WRAPPER_FRAGMENTS = (
    os.path.join("monitor", "xray", "ledger.py"),
    os.path.join("parallel", "pipeline", "p2p.py"),
    os.sep + "site-packages" + os.sep,
)


def jaxpr_pass(name: str):
    """Register a pass under ``name`` (decorator)."""

    def register(fn):
        JAXPR_PASSES[name] = fn
        return fn

    return register


def _relsite(path: str, line: int) -> str:
    """Normalize an absolute source path to a repo-relative site string."""
    path = path.replace(os.sep, "/")
    for anchor in ("/apex_tpu/", "/examples/", "/tests/", "/benchmarks/"):
        idx = path.rfind(anchor)
        if idx >= 0:
            return f"{path[idx + 1:]}:{line}"
    root = _REPO_ROOT.replace(os.sep, "/")
    if path.startswith(root + "/"):
        return f"{path[len(root) + 1:]}:{line}"
    return f"{path}:{line}"


def eqn_site(eqn, skip_wrappers: bool = True) -> str:
    """Repo-relative ``file.py:line`` of the user code that produced an
    equation, or ``"<unknown>"`` when source info is unavailable.

    Note one honest quirk: equations synthesized by transposition
    (backward-pass converts, reversed scan edges) inherit the FORWARD
    equation's source info, so a backward promotion points at the forward
    cast it transposes — the right line to look at anyway.
    """
    from jax._src import source_info_util

    # private API (jax exposes no public equation -> user frame lookup);
    # it takes the equation's Traceback, not its SourceInfo
    frames = list(source_info_util.user_frames(eqn.source_info.traceback))
    chosen = None
    for fr in frames:
        chosen = fr
        if skip_wrappers and any(
            frag in fr.file_name for frag in _WRAPPER_FRAGMENTS
        ):
            continue
        break
    if chosen is None:
        return "<unknown>"
    return _relsite(chosen.file_name, chosen.start_line)


def _subjaxprs(eqn) -> Iterator[Any]:
    """Every jaxpr nested in an equation's params (pjit/scan/cond/shard_map
    bodies, custom_vjp rules, remat) — duck-typed on ``.eqns``."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            j = getattr(v, "jaxpr", v)  # ClosedJaxpr -> Jaxpr
            if hasattr(j, "eqns"):
                yield j


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Depth-first over every equation of ``jaxpr`` (Jaxpr or ClosedJaxpr)
    including all nested sub-jaxprs."""
    j = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in j.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub)


def lower_step(fn, args, donate_argnums=None):
    """The auditors' ONE AOT lowering recipe (donation, the HLO comms
    differ, the sharding auditor all read products of this — keep them
    agreeing):

    - a DECLARED donation intent always builds a fresh
      ``jax.jit(fn, donate_argnums=..., keep_unused=True)``, even over a
      prejitted ``fn`` — keep_unused makes HLO parameters map 1:1 onto
      flat input leaves, which the donation auditor's indexing needs;
    - otherwise a prejitted ``fn`` lowers as-is (its own donation marks
      are the thing under audit), and a plain function gets
      ``keep_unused=True`` with no donation.
    """
    if donate_argnums:
        return jax.jit(
            fn, donate_argnums=tuple(donate_argnums), keep_unused=True
        ).lower(*args)
    if hasattr(fn, "lower"):  # only jit stages carry .lower
        return fn.lower(*args)
    return jax.jit(fn, keep_unused=True).lower(*args)


@dataclasses.dataclass
class StepTarget:
    """A step function prepared for auditing: what the CLI and tests hand
    to :func:`run_passes`.

    ``args`` may be concrete arrays or ``ShapeDtypeStruct``s; nothing is
    executed. ``donate_argnums`` is the donation INTENT the donation
    auditor verifies against XLA's realized aliasing (None disables that
    pass for the target — e.g. an inference step with nothing to donate).
    """

    name: str
    fn: Callable
    args: Tuple = ()
    mesh: Optional[jax.sharding.Mesh] = None
    donate_argnums: Optional[Tuple[int, ...]] = None
    #: dtypes considered "low precision" for the precision auditor; a
    #: promotion OUT of these to f32/f64 is flagged
    low_dtypes: Tuple = (jnp.bfloat16, jnp.float16)
    #: the analytic HBM prediction (an ``xray.hbm.model.HbmBreakdown``)
    #: the ``hlo-memory`` differ reconciles against XLA's
    #: ``memory_analysis()``; None disables exact reconciliation for the
    #: target (the pass reports ``memory.unverifiable`` instead)
    hbm: Optional[Any] = None
    #: per-target floors for the sharding/donation auditors; None uses
    #: each auditor's 1 MiB default. The tiny CLI targets sit far below
    #: that on purpose — the seeded autofix target lowers the floors so
    #: its deliberately replicated flat opt-state buffers are flagged
    sharding_min_bytes: Optional[int] = None
    donation_min_bytes: Optional[int] = None
    #: autofix hooks (analysis/autofix): ``builder(mesh, **overrides)``
    #: rebuilds this target with injected specs/donations ("specs are
    #: data"); ``build_overrides`` records what this instance was built
    #: with. ``spec_slots`` maps an argnum to the builder kwarg naming
    #: that argument's PartitionSpec; ``donate_slot`` names the builder
    #: kwarg taking the donate tuple. A target with no builder is not
    #: auto-fixable — the applier prints prescriptions instead.
    builder: Optional[Callable] = None
    build_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    spec_slots: Dict[int, str] = dataclasses.field(default_factory=dict)
    donate_slot: Optional[str] = None


class StepContext:
    """What a pass sees: the target plus its lazily-traced jaxpr."""

    def __init__(self, target: StepTarget):
        self.target = target
        self._jaxpr = None
        self._aot = None
        self._hlo_module = None

    @property
    def name(self) -> str:
        return self.target.name

    @property
    def fn(self):
        return self.target.fn

    @property
    def args(self):
        return self.target.args

    @property
    def mesh(self):
        return self.target.mesh

    @property
    def donate_argnums(self):
        return self.target.donate_argnums

    @property
    def low_dtypes(self):
        return tuple(jnp.dtype(d) for d in self.target.low_dtypes)

    @property
    def jaxpr(self):
        """The closed jaxpr of the step, traced once and cached. Tracing
        is abstract (``jax.make_jaxpr``) — no devices are touched, which
        is what makes the auditors CPU-safe pre-flight checks."""
        if self._jaxpr is None:
            fn = self.fn
            # a jit-wrapped step (only jit stages carry .lower) is
            # unwrapped one level so the walk starts at the program, not
            # at a single opaque pjit equation (the predict_comms
            # pattern); shard_map wrappers must stay on — they carry the
            # mesh context the body needs
            if hasattr(fn, "lower"):
                fn = getattr(fn, "__wrapped__", fn)
            self._jaxpr = jax.make_jaxpr(fn)(*self.args)
        return self._jaxpr

    def aot(self):
        """``(lowered, compiled)`` of the step, built once and shared by
        every pass that reads compile products (donation, the HLO comms
        differ, the sharding auditor) — the compile is the only
        non-tracing cost in the whole gate, so it is paid once per
        target. Lowering follows :func:`lower_step` exactly (declared
        donation intent wins, ``keep_unused=True`` for 1:1 leaf↔param
        mapping) so every consumer reads the same module."""
        if self._aot is None:
            lowered = lower_step(self.fn, self.args, self.donate_argnums)
            self._aot = (lowered, lowered.compile())
        return self._aot

    def hlo_module(self):
        """The parsed optimized-HLO module of :meth:`aot`'s executable,
        parsed once and shared by every compile-product pass (donation's
        realized aliases, the comms differ, the sharding auditor) — on a
        real model ``.as_text()`` serializes tens of MB, so text + parse
        are paid once per target, like the compile itself. Raises
        ``ValueError`` on unparseable HLO; callers downgrade that to
        their own unverifiable outcome."""
        if self._hlo_module is None:
            from apex_tpu.analysis.hlo import parser as hlo_parser

            _, compiled = self.aot()
            self._hlo_module = hlo_parser.parse_hlo_module(
                hlo_parser.module_text(compiled)
            )
        return self._hlo_module

    def iter_eqns(self) -> Iterator[Any]:
        return iter_eqns(self.jaxpr)

    def finding(self, rule: str, message: str, **kw) -> Finding:
        kw.setdefault("target", self.name)
        return Finding(rule=rule, message=message, **kw)


def run_passes(
    target: StepTarget,
    passes: Optional[Sequence[str]] = None,
    allowlist: Optional[Allowlist] = None,
) -> List[Finding]:
    """Run ``passes`` (default: all registered) over one target and return
    the merged raw findings; apply an allowlist afterwards via
    ``allowlist.apply`` (kept separate so the CLI can pool findings from
    several targets before the stale-entry check)."""
    names = list(passes) if passes is not None else sorted(JAXPR_PASSES)
    unknown = [n for n in names if n not in JAXPR_PASSES]
    if unknown:
        raise KeyError(
            f"unknown jaxpr pass(es) {unknown}; registered: "
            f"{sorted(JAXPR_PASSES)}"
        )
    ctx = StepContext(target)
    findings: List[Finding] = []
    for name in names:
        findings.extend(JAXPR_PASSES[name](ctx))
    merged = merge_findings(findings)
    if allowlist is not None:
        return allowlist.apply(merged, check_stale=False).findings
    return merged


# importing the pass modules registers them; keep at the bottom so the
# registry and decorators above exist first
from apex_tpu.analysis import precision as _precision  # noqa: E402,F401
from apex_tpu.analysis import donation as _donation  # noqa: E402,F401
from apex_tpu.analysis import collectives as _collectives  # noqa: E402,F401
from apex_tpu.analysis import host_sync as _host_sync  # noqa: E402,F401
from apex_tpu.analysis.hlo import comms_diff as _comms_diff  # noqa: E402,F401
from apex_tpu.analysis.hlo import sharding_audit as _sharding_audit  # noqa: E402,F401
from apex_tpu.analysis.hlo import memory_diff as _memory_diff  # noqa: E402,F401
