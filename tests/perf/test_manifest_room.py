"""Room for a second serving cell: ``BENCHMARK.json`` with one more served
model's configuration, cell and readers appended, as a ``model_config`` PR
would add them (new files, new entries, the cell's name appended to the
``workloads`` of ``tpot_p50_ms`` and of the serving readers that apply to any
served model), passes every rule of ``test_manifest.py`` and
``test_manifest_serving.py``; the same copy with the cell among the training
cells, or reporting no end-to-end metric but ``setup_s``, does not."""

import copy
import json
import os

import pytest

from _bench import FIXTURES, PERF, REPO, benchmark
import test_manifest as tm
import test_manifest_serving as tms

CELL = "hybrid_tiny.chat"
CONFIG = {"name": "hybrid_tiny",
          "source": "https://huggingface.co/openai-community/gpt2",
          "file": "perf/configs/hybrid_tiny.json", "reduced": ["layers_kept"],
          "why": "a second served model: rotary positions over a paged pool"}
CONFIG_FILE = {"source": CONFIG["source"], "reduced": CONFIG["reduced"],
               "n_layer": 4, "n_embd": 256, "n_head": 4, "n_positions": 64,
               "vocab_size": 2000, "layers_kept": 4,
               "assumed": {"padded_vocab_size": 2048}}
WORKLOAD = {"name": CELL, "config": "hybrid_tiny", "traffic": "chat",
            "chips": 1, "why": "a second serving cell: short chat turns"}
CELL_FILE = {"config": "hybrid_tiny", "driver": "gpt_rope_serve",
             "chips": 1,
             "engine": {"lanes": 4, "block_size": 8, "num_blocks": 32,
                        "max_seq_len": 64},
             "traffic": {"rate_rps": 2.0},
             "limits": {"served_gap_max": 0.03}}
NEW_READERS = [
    {"name": "paged_decode_roofline", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels", "moves": "tpot_p50_ms",
     "workloads": [CELL]},
    {"name": "decode_mixer_ms.serve", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "model", "moves": "tpot_p50_ms",
     "workloads": [CELL]},
]
READER = '''"""A fixture reader."""


def read(ctx):
    from perf import trace_reduce

    return trace_reduce.per_run_ms(ctx.reduction, "jit_decode",
                                   "scope_seconds", ("moe_experts",))
'''


def with_a_second_serving_cell(bench):
    """The manifest as a serving ``model_config`` PR leaves it."""
    bench = copy.deepcopy(bench)
    bench["configs"].append(dict(CONFIG))
    bench["workloads"].append(dict(WORKLOAD))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if (m["name"] == "tpot_p50_ms" or tms.CELL in m.get("workloads", ())
                and m["name"] != "decode_copy_share.serve"):
            m["workloads"].append(CELL)
    bench["per_layer"] += copy.deepcopy(NEW_READERS)
    return bench


def _root(tmp_path, bench):
    """A checkout that holds ``bench`` and the files it names: the
    repository's by symlink, the new cell's written."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "perf"))
    os.makedirs(os.path.join(root, "tests"))
    os.symlink(os.path.join(REPO, "tests", "perf"),
               os.path.join(root, "tests", "perf"))
    os.symlink(os.path.join(REPO, "PERF.md"), os.path.join(root, "PERF.md"))
    for entry in os.listdir(PERF):
        src = os.path.join(PERF, entry)
        if entry == "__pycache__":
            continue
        if entry in ("workloads", "configs", "layer_metrics", "drivers"):
            os.makedirs(os.path.join(root, "perf", entry))
            for f in os.listdir(src):
                if f != "__pycache__":
                    os.symlink(os.path.join(src, f),
                               os.path.join(root, "perf", entry, f))
            continue
        os.symlink(src, os.path.join(root, "perf", entry))
    with open(os.path.join(FIXTURES, "gpt_rope_serve.py")) as f:
        driver = f.read()
    files = [("BENCHMARK.json", json.dumps(bench, indent=2)),
             (CONFIG["file"], json.dumps(CONFIG_FILE)),
             (f"perf/workloads/{CELL}.json", json.dumps(CELL_FILE)),
             ("perf/drivers/gpt_rope_serve.py", driver)]
    files += [(f"perf/layer_metrics/{m['name']}.py", READER)
              for m in NEW_READERS]
    for rel, text in files:
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    return root


def every_rule(bench, root):
    """Every rule of the two manifest files, on ``bench`` under ``root``."""
    with open(os.path.join(PERF, "workloads", tms.CELL + ".json")) as f:
        chat = json.load(f)
    tm.test_parses_with_exactly_the_contracts_keys(bench, root)
    tm.test_command_names_a_file_under_paths(bench, root)
    tm.test_names_units_and_lengths(bench)
    tm.test_every_config_is_used_and_its_file_states_its_sizes(bench, root)
    tm.test_every_cells_files_exist(bench, root)
    tm.test_every_cell_reports_what_its_layer_metrics_move(bench)
    tm.test_layer_names_are_perf_mds(bench, root)
    for row in tm.PR32:
        tm.test_a_new_metric_has_its_entry_and_its_reader(bench, *row)
    tm.test_the_metrics_that_stood_before_still_stand(bench)
    tms.test_the_cell_has_its_entry_and_its_files(bench, chat)
    tms.test_the_cell_serves_the_published_model_at_the_size_named(bench,
                                                                   chat)
    tms.test_the_end_to_end_metric_belongs_to_the_cell_alone(bench, root)
    for row in tms.PR33:
        tms.test_a_serving_metric_has_its_entry_and_its_reader(bench, *row)
    tms.test_the_whole_paths_share_of_the_peak_stands_beside_the_readers(
        bench)
    tms.test_nothing_that_stood_before_was_edited(bench)


def test_the_manifest_as_it_stands_passes_every_rule():
    every_rule(benchmark(), REPO)


def test_a_copy_with_a_second_serving_cell_passes_every_rule(tmp_path):
    bench = with_a_second_serving_cell(benchmark())
    assert bench["workloads"][-1]["name"] == CELL
    assert next(m for m in bench["per_layer"] if m["name"] ==
                "decode_copy_share.serve")["workloads"] == [tms.CELL]
    every_rule(bench, _root(tmp_path, bench))


def test_a_second_serving_cell_among_the_training_cells_fails(tmp_path):
    bench = with_a_second_serving_cell(benchmark())
    bench["end_to_end"][0]["workloads"].append(CELL)
    root = _root(tmp_path, bench)
    with pytest.raises(AssertionError):
        tms.test_the_end_to_end_metric_belongs_to_the_cell_alone(bench, root)
    with pytest.raises(AssertionError):
        every_rule(bench, root)


def test_a_serving_cell_in_no_end_to_end_list_fails(tmp_path):
    """The cell and its readers as before, but its name appended nowhere:
    it reports ``setup_s`` alone."""
    bench = benchmark()
    bench["configs"].append(dict(CONFIG))
    bench["workloads"].append(dict(WORKLOAD))
    root = _root(tmp_path, bench)
    with pytest.raises(AssertionError):
        tm.test_every_cell_reports_what_its_layer_metrics_move(bench)
    with pytest.raises(AssertionError):
        every_rule(bench, root)
