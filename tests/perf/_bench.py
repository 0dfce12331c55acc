"""Shared by the benchmark's tests: the repo's paths and ``perf/run.py``
loaded as a module (``perf/`` is a directory of files, not a package the
tests may assume is importable)."""

import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PERF = os.path.join(REPO, "perf")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def load(relpath, name=None):
    """Import ``perf/<relpath>`` (or a file by its absolute path) by
    path."""
    path = os.path.join(PERF, relpath)
    name = name or "perf_test_" + relpath.replace("/", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def fixture_root(tmp_path, workloads, configs, metrics=(), extra_files=()):
    """A benchmark root in ``tmp_path``: the real ``perf/`` code by symlink,
    and a ``BENCHMARK.json`` that names only fixture cells. ``workloads`` and
    ``configs`` map a name to the dict its file holds; ``metrics`` are
    ``BENCHMARK.json`` metric entries (end-to-end ones have no ``moves``);
    ``extra_files`` are (relative path, text) pairs written under the root.
    The readers and the drivers are linked file by file, so that a fixture
    can bring a reader or a driver of its own (``perf/drivers/<name>.py``
    in ``extra_files``) without writing into the repository."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "perf"))
    for sub in ("workloads", "configs", "layer_metrics", "drivers"):
        os.makedirs(os.path.join(root, "perf", sub))
    for entry in os.listdir(PERF):
        src = os.path.join(PERF, entry)
        if entry in ("workloads", "configs", "__pycache__"):
            continue
        if entry in ("layer_metrics", "drivers"):
            for f in os.listdir(src):
                if f.endswith(".py"):
                    os.symlink(os.path.join(src, f),
                               os.path.join(root, "perf", entry, f))
            continue
        os.symlink(src, os.path.join(root, "perf", entry))
    for name, body in workloads.items():
        with open(os.path.join(root, "perf", "workloads",
                               name + ".json"), "w") as f:
            json.dump(body, f)
    for name, body in configs.items():
        with open(os.path.join(root, "perf", "configs",
                               name + ".json"), "w") as f:
            json.dump(body, f)
    for rel, text in extra_files:
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    bench = {
        "command": ["python3", "perf/run.py"], "paths": ["perf"],
        "run_seconds": 1,
        "configs": [{"name": n, "source": "fixture",
                     "file": f"perf/configs/{n}.json", "reduced": [],
                     "why": "fixture"} for n in configs],
        "workloads": [{"name": n, "config": b["config"],
                       "traffic": n.split(".", 1)[1], "chips": 1,
                       "why": "fixture"} for n, b in workloads.items()],
        "end_to_end": [m for m in metrics if "moves" not in m],
        "per_layer": [m for m in metrics if "moves" in m],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


GPT_TINY = {"name": "gpt_tiny", "n_layer": 2, "n_embd": 64, "n_head": 4,
            "n_positions": 32, "vocab_size": 120,
            "assumed": {"padded_vocab_size": 128}}

PRETRAIN_TINY = {
    "config": "gpt_tiny", "driver": "gpt_pretrain", "chips": 1,
    "seq_len": 32, "micro_batch": 2, "global_batch": 4,
    "corpus_samples": 16, "reference_rows_per_block": 2,
    "spans": ["fetch_batch", "step", "fetch_loss"], "trace_seconds": 0.3,
    # set as the cells' are (PERF.md 2), from this fixture's own readings on
    # the CPU: the bf16 program reads at most 3.7e-5 / 0.011 / 0.018, the
    # fp8 control at least 1.5e-4 on the loss, half a batch 0.41 / 0.12
    "limits": {"loss_gap_step2": 1e-4, "grad_norm_gap": 0.05,
               "update_norm_gap": 0.06},
}

def e2e(name, unit):
    return {"name": name, "unit": unit, "better": "lower", "bound": 0.05,
            "source": "host_clock"}


def layer(name, unit, moves, cells):
    return {"name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "fixture", "moves": moves,
            "workloads": list(cells)}
