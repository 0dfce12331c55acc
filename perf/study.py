#!/usr/bin/env python3
"""Readings that the limits of a cell's output check are set from, and the
proof at the cell's own size that the check fails what it has to.

    python3 perf/study.py --workload <cell> --seeds 11,12,13 [--controls 3]

Runs the cell's driver's ``study``: on every seed the program against the
reference (the lower readings), and on the first ``--controls`` seeds the
control (the reference in the nearest lower precision, put in the program's
place) and the planted faults against the reference (the upper readings).
Each is judged through the cell's own limits by the comparison a run uses, so
its ``correct`` is what a run would have printed: true for the program, false
for the control and for every fault. One process, on the chip at the cell's
own size; prints one JSON line per reading, then by kind how many read
correct and the smallest and largest of each number. Exit code 1 if a program
reading is not correct or a control or fault is. Not part of a benchmark
run.
"""

import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    args = p.parse_args(argv)
    root = run.ROOT
    bench = run._load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next(w for w in bench["workloads"]
                 if w["name"] == args.workload)
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cell = run._load_json(os.path.join(
        root, "perf", "workloads", args.workload + ".json"))
    config = run._load_json(os.path.join(root, cfg["file"]))
    device = run._device_entry(int(entry["chips"]), allow_cpu=False)
    run._enable_compile_cache(root)
    ctx = run.Context(root, cell, config, 0, 0.0, 0, device, None)
    driver = run.load_module(root, "drivers", cell["driver"])
    compare = run.load_module(root, "compare")
    seeds = [int(s) for s in args.seeds.split(",")]
    verdicts = collections.defaultdict(list)
    table = collections.defaultdict(lambda: collections.defaultdict(list))
    for kind, seed, compared, readings in driver.study(
            cell, config, seeds, ctx, controls=args.controls):
        ok = compare.correct(compared)
        verdicts[kind].append(ok)
        print(json.dumps({"kind": kind, "seed": seed, "correct": ok,
                          "compared": compared, "readings": readings}),
              flush=True)
        for c in readings:
            table[kind][c["name"]].append(c["value"])
    as_expected = True
    for kind, names in table.items():
        oks = verdicts[kind]
        print(f"{kind}: correct in {sum(oks)} of {len(oks)}", flush=True)
        as_expected &= all(oks) if kind == "program" else not any(oks)
        for name, values in names.items():
            print(f"{kind:22s} {name:18s} n={len(values):2d} "
                  f"min {min(values):.6g}  max {max(values):.6g}",
                  flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
