"""``python -m apex_tpu.monitor.xray.timeline <logdir>`` — analyze a capture.

Standalone device-time breakdown of any ``jax.profiler`` capture (a
``ProfilerTrigger`` window, a ``utils.trace`` block, a TensorBoard
profile dir): per-step compute/collective/exposed/idle partition,
overlap and bubble fractions. Exit status: 0 on a successful analysis
with at least one step, 1 when no trace files / no device ops were
found (so CI can gate on "the capture was analyzable").

``--hlo PATH`` joins the capture to the compiled step's text (what
``pretrain_gpt.py --profile-analyze`` writes beside its capture as
``step.hlo.txt``, ``benchmarks/capture_cell.py`` beside a benchmark
cell's): device self time by step phase (forward / backward / unscale /
optimizer / guard), by Pallas kernel and by flax module, the share no
rule could place, and the device's idle gaps by the host annotation
that covers them (the goodput phases; ``--annotation NAME`` adds
others). docs/observability.md "Reading a chip capture".

The bandwidth join needs the mesh as well, which a bare log dir does not
carry — run the examples with ``--profile-analyze`` for that report, or
call ``timeline.analyze_logdir(logdir, module=..., mesh=...,
ledger=...)`` programmatically.

Flags: ``--json PATH`` appends the ``kind="profile"`` records to a
jsonl (the shared MetricRouter schema); ``--schedule NAME --pp P
--microbatches M [--chunks V]`` joins the pipeline schedule algebra's
predicted bubble fraction (``parallel/pipeline/algebra.py``) onto every
per-step record and the summary — the predicted-vs-measured bubble
join, computable from a bare log dir because the algebra needs only
(schedule, P, M, V), not the HLO.
"""

import argparse
import sys

#: the registered schedule names (parallel.pipeline.algebra.SCHEDULES),
#: spelled literally: algebra.py itself is jax-free but importing it
#: initializes the parallel package, which is not — and argparse needs
#: the choices before anyone passes --schedule. Kept in sync by
#: tests/test_timeline.py (drift fails tier-1).
_SCHEDULE_CHOICES = ("1f1b", "interleaved", "no_pipelining", "zero_bubble")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.monitor.xray.timeline",
        description="device-time breakdown of a jax.profiler capture",
    )
    p.add_argument("logdir", help="profiler log dir (the dir passed to "
                   "jax.profiler.trace / ProfilerTrigger)")
    p.add_argument("--json", default=None,
                   help="append kind='profile' records to this jsonl")
    p.add_argument("--hlo", default=None,
                   help="the compiled step's HLO text: joins device time "
                   "to step phases, Pallas kernels and modules")
    p.add_argument("--annotation", action="append", default=[],
                   help="a host TraceAnnotation name to put idle gaps down "
                   "to, beside the goodput phases (repeatable)")
    p.add_argument("--top", type=int, default=12,
                   help="rows of the by-module and by-op tables (with "
                        "--hlo); default 12")
    p.add_argument("--schedule", default=None, choices=_SCHEDULE_CHOICES,
                   help="pipeline schedule name for the predicted-bubble "
                   "join")
    p.add_argument("--pp", type=int, default=None,
                   help="pipeline size P for the join")
    p.add_argument("--microbatches", type=int, default=None,
                   help="microbatch count M for the join")
    p.add_argument("--chunks", type=int, default=1,
                   help="virtual-PP model chunks V for the join")
    args = p.parse_args(argv)

    predicted = None
    if args.schedule is not None:
        if args.pp is None or args.microbatches is None:
            p.error("--schedule needs --pp and --microbatches")
        from apex_tpu.parallel.pipeline.algebra import schedule_cost

        try:
            predicted = schedule_cost(
                args.schedule, args.pp, args.microbatches, args.chunks
            ).bubble_fraction
        except ValueError as e:
            # e.g. interleaved without --chunks >= 2, or M % P != 0 —
            # a usage message, not a traceback
            p.error(str(e))

    from apex_tpu.monitor.goodput.spans import PHASES
    from apex_tpu.monitor.xray.timeline.analyzer import analyze_logdir

    module = None
    if args.hlo is not None:
        from apex_tpu.analysis.hlo.parser import parse_hlo_module

        try:
            with open(args.hlo) as f:
                module = parse_hlo_module(f.read())
        except OSError as e:
            print(f"timeline: {e}", file=sys.stderr)
            return 1
    try:
        report = analyze_logdir(
            args.logdir, module=module, predicted_bubble_fraction=predicted,
            schedule=args.schedule,
            annotations=PHASES + tuple(args.annotation),
        )
    except (FileNotFoundError, ValueError) as e:
        print(f"timeline: {e}", file=sys.stderr)
        return 1
    for path in report.files:
        print(f"trace: {path}", flush=True)
    print(report.summary(top=args.top), flush=True)
    if args.json:
        from apex_tpu.monitor.router import JsonlSink

        sink = JsonlSink(args.json)
        for rec in report.to_records():
            sink.emit(rec)
        sink.close()
    return 0 if report.steps else 1


if __name__ == "__main__":
    sys.exit(main())
