"""Run one benchmark cell traced and keep what the run throws away: the
profiler capture and the compiled step's text.

    python benchmarks/capture_cell.py --workload gpt2_345m.pretrain \
        --seed 7 --seconds 20 --out chiprun_out/capture

``perf/run.py --trace 1`` reduces its capture to a handful of numbers and
deletes it. This runs the same cell through the same code (``run_cell``,
nothing of the harness edited) and, before the capture goes, copies it to
``<out>/`` (the ``plugins/profile/<run>/`` layout the profiler wrote) with
the compiled step's HLO text beside it as ``<out>/step.hlo.txt``. The
result line is printed as ``perf/run.py`` prints it and also written to
``<out>/result.json``: its ``device.busy_s`` is the benchmark's reading of
the very capture that

    python -m apex_tpu.monitor.xray.timeline <out> --hlo <out>/step.hlo.txt

then reads with the program's own names (docs/observability.md "Reading a
chip capture"). A driver whose state has no compiled ``step`` leaves no
text, and the capture is kept all the same.
"""

import argparse
import importlib.util
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_run(root):
    path = os.path.join(root, "perf", "run.py")
    spec = importlib.util.spec_from_file_location("perf_run_capture", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def capture(root, workload, seed, seconds, out_dir, allow_cpu=False):
    """One traced run of ``workload``; returns its result line."""
    run = _load_run(root)
    os.makedirs(out_dir, exist_ok=True)

    drop = run.Context.drop_trace

    def keep_then_drop(ctx):
        src = os.path.join(ctx._trace_dir, "plugins")
        if os.path.isdir(src):
            shutil.rmtree(os.path.join(out_dir, "plugins"),
                          ignore_errors=True)
            shutil.copytree(src, os.path.join(out_dir, "plugins"))
        drop(ctx)

    load_module = run.load_module

    def load_and_tap(root_, *relpath):
        module = load_module(root_, *relpath)
        if relpath[0] == "drivers" and not hasattr(module, "_capture_tap"):
            setup = module.setup

            def setup_and_dump(*args, **kwargs):
                state = setup(*args, **kwargs)
                step = getattr(state, "step", None)
                if hasattr(step, "as_text"):
                    from apex_tpu.analysis.hlo.parser import module_text

                    with open(os.path.join(out_dir, "step.hlo.txt"),
                              "w") as f:
                        f.write(module_text(step))
                return state

            module.setup = setup_and_dump
            module._capture_tap = True
        return module

    run.Context.drop_trace = keep_then_drop
    run.load_module = load_and_tap
    try:
        line = run.run_cell(root, workload, seed, seconds, 1,
                            allow_cpu=allow_cpu)
    finally:
        run.Context.drop_trace = drop
        run.load_module = load_module
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(line, f)
    return line


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    capture(ROOT, args.workload, args.seed, args.seconds, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
