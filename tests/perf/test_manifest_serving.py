"""What PR 33 added to BENCHMARK.json: the serving cell, its end-to-end
metric and its readers, each with its file, its cells and what it moves;
and that nothing the manifest held before was edited for them."""

import json
import os
import types

import pytest

from _bench import PERF, REPO, benchmark, load

CELL = "gpt2_345m.chat"
TPOT = "tpot_p50_ms"
#: the first token's readers: per-layer, and their layer says that nothing
#: judged moves with them yet (PERF.md 2 and 3)
FIRST = "serving engine, first token: nothing judged yet"

#: (name, unit, better, source, layer, moves)
PR33 = [
    ("mfu_pct.serve", "%", "higher", "host_clock", "serving engine", TPOT),
    ("device_idle_pct.serve", "%", "lower", "device_trace", "device", TPOT),
    ("decode_tick_ms.serve", "ms", "lower", "device_trace",
     "serving engine", TPOT),
    ("decode_copy_share.serve", "share", "lower", "device_trace",
     "serving engine", TPOT),
    ("prefill_share_pct.serve", "%", "lower", "device_trace",
     "serving engine", TPOT),
    ("tpot_p95_ms.serve", "ms", "lower", "host_clock", "serving engine",
     TPOT),
    ("queue_wait_p95_ms.serve", "ms", "lower", "host_clock", FIRST, TPOT),
    ("ttft_mean_ms.serve", "ms", "lower", "host_clock", FIRST, TPOT),
    ("ttft_p50_ms.serve", "ms", "lower", "host_clock", FIRST, TPOT),
    ("ttft_p95_ms.serve", "ms", "lower", "host_clock", FIRST, TPOT),
    ("kv_pool_peak_share.serve", "share", "higher", "program_counter",
     FIRST, TPOT),
    ("kv_pool_held_share.serve", "share", "higher", "program_counter",
     FIRST, TPOT),
    ("loadgen_late_p95_ms.serve", "ms", "lower", "host_clock",
     "load generator", TPOT),
]


@pytest.fixture(scope="module")
def bench():
    return benchmark()


@pytest.fixture(scope="module")
def cell():
    with open(os.path.join(PERF, "workloads", CELL + ".json")) as f:
        return json.load(f)


def test_the_cell_has_its_entry_and_its_files(bench, cell):
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["config"] == "gpt2_345m" and entry["traffic"] == "chat"
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert cell["driver"] == "gpt_serve"
    for rel in ("drivers/gpt_serve.py", "loadgen_requests.py",
                "reference/gpt_serving.py", "compare_serving.py",
                "serve_flops.py", "serve_trace.py"):
        assert os.path.isfile(os.path.join(PERF, rel)), rel


def test_the_cell_serves_the_published_model_at_the_size_named(bench, cell):
    with open(os.path.join(REPO, "perf/configs/gpt2_345m.json")) as f:
        config = json.load(f)
    e = cell["engine"]
    assert (e["lanes"], e["block_size"], e["max_seq_len"]) == (24, 16, 1024)
    assert e["max_seq_len"] <= config["n_positions"]
    # a pool no larger than the lanes can ever fill
    assert e["num_blocks"] <= e["lanes"] * e["max_seq_len"] // e["block_size"]
    assert "learned" in cell["model"]["positions"]
    # 24 layers x (k + v) x 1024 wide x 2 bytes
    assert cell["model"]["kv_bytes_per_token"] == (
        config["n_layer"] * 2 * config["n_embd"] * 2)
    t = cell["traffic"]
    # lengths of a named public trace, cut to the positions the model has
    assert "AzurePublicDataset" in t["source"] and "2311.18677" in t["source"]
    assert (t["prompt"]["median"], t["answer"]["median"]) == (1020, 129)
    assert t["context_max"] == e["max_seq_len"]
    assert t["prompt"]["max"] + t["answer"]["min"] <= t["context_max"]
    assert t["prompt"]["min"] + t["answer"]["max"] <= t["context_max"]
    assert t["cuts"] and t["assumed"]
    assert t["temperature"] == 0.0 and t["shared_prefixes"] is False
    assert cell["check_requests"] == 12
    assert cell["limits"] and all(v > 0 for v in cell["limits"].values())


def test_the_end_to_end_metric_belongs_to_the_cell_alone(bench):
    """The median token gap is the cell's end-to-end metric; its tail and
    every statistic of the first token spread too widely for a bound there
    is (PERF.md 2) and are read per layer."""
    assert [m["name"] for m in bench["end_to_end"]] == [
        "train_step_ms", "setup_s", TPOT]
    m = next(m for m in bench["end_to_end"] if m["name"] == TPOT)
    assert m["workloads"] == [CELL]
    assert (m["unit"], m["better"], m["source"]) == ("ms", "lower",
                                                     "host_clock")
    assert 0.01 <= m["bound"] <= 0.1
    train = next(m for m in bench["end_to_end"]
                 if m["name"] == "train_step_ms")
    assert CELL not in train["workloads"]


@pytest.mark.parametrize("name,unit,better,source,layer,moves", PR33,
                         ids=[m[0] for m in PR33])
def test_a_serving_metric_has_its_entry_and_its_reader(
        bench, name, unit, better, source, layer, moves):
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": [CELL]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e[moves]["workloads"]
    # below the knee the token gap does not move with the load: a reader of
    # the first token's side says so in its layer
    assert (layer == FIRST) == any(k in name for k in (
        "ttft", "queue", "kv_pool"))
    reader = load(f"layer_metrics/{name}.py")
    assert callable(reader.read) and reader.__doc__
    # a reader that finds nothing to read returns nothing, never 0
    assert reader.read(types.SimpleNamespace(
        reduction=None, counters={}, peaks=None, chips=1, config={},
        cell={"programs": {"decode": "jit_decode", "prefill": "jit_prefill"},
              "copy_families": ["copy"]},
        device={"platform": "cpu"})) is None


def test_the_whole_paths_share_of_the_peak_stands_beside_the_readers(bench):
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert {m["name"] for m in mine} == {m[0] for m in PR33}
    assert any("mfu" in m["name"] and m["unit"] == "%" for m in mine)


def test_mfu_reads_the_drivers_count_over_the_window():
    reader = load("layer_metrics/mfu_pct.serve.py")
    ctx = types.SimpleNamespace(
        counters={"model_flops": 197e12 * 0.5, "window_s": 50.0},
        peaks={"bf16_flops_per_s": 197e12}, chips=1)
    assert reader.read(ctx) == pytest.approx(1.0)


def test_nothing_that_stood_before_was_edited(bench):
    """The cells, metrics and bounds of the parent, entry for entry, at the
    head of each list: the serving cell came as additions."""
    assert [w["name"] for w in bench["workloads"]][:3] == [
        "gpt2_345m.pretrain", "gpt2_345m.pretrain_dp4",
        "joyai_llm_flash.pretrain"]
    assert bench["end_to_end"][0] == {
        "name": "train_step_ms", "unit": "ms", "better": "lower",
        "bound": 0.01, "source": "host_clock",
        "workloads": ["gpt2_345m.pretrain", "gpt2_345m.pretrain_dp4",
                      "joyai_llm_flash.pretrain"]}
    assert bench["end_to_end"][1] == {
        "name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
        "source": "host_clock"}
    assert len(bench["per_layer"]) == 17 + len(PR33)
    assert all(CELL not in m["workloads"] for m in bench["per_layer"][:17])
    assert bench["run_seconds"] == 50 and bench["paths"] == ["perf",
                                                             "tests/perf"]
