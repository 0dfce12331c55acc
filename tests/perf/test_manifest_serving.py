"""What PR 33 added to BENCHMARK.json: the serving cell, its end-to-end
metric and its readers, each with its file, its cells and what it moves;
the rules every serving cell is held to, which a later serving cell joins
by appending its name; and that nothing the manifest held before was edited
for them. Each rule is a function of the manifest's dict (and of the root
its files lie under), so that ``test_manifest_room.py`` can hold a manifest
with a cell more to the same rules."""

import json
import os
import types

import pytest

from _bench import FIXTURES, PERF, REPO, benchmark, load

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


def serving_cells(bench):
    """The cells ``tpot_p50_ms`` lists: every serving cell, and only those."""
    return next(m for m in bench["end_to_end"]
                if m["name"] == TPOT)["workloads"]


def test_the_cell_has_its_entry_and_its_files(bench, cell):
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["config"] == "gpt2_345m" and entry["traffic"] == "chat"
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert cell["driver"] == "gpt_serve"
    for rel in ("drivers/gpt_serve.py", "loadgen_requests.py",
                "reference/gpt_serving.py", "compare_serving.py",
                "serve_flops.py", "serve_trace.py", "serve_window.py",
                "reference/served.py"):
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


def test_the_end_to_end_metric_belongs_to_the_cell_alone(bench, root=REPO):
    """The median token gap is the serving cells' end-to-end metric (the
    chat cell's first); its tail and every statistic of the first token
    spread too widely for a bound there is (PERF.md 2) and are read per
    layer. Every cell it lists is a serving cell: its file gives the
    engine and a fixed rate, and its driver runs the one serving window
    every served model shares (``perf/serve_window.py``), so that every
    serving cell's token gap has one definition. No training cell is in its
    list, and no serving cell in ``train_step_ms``'s."""
    assert [m["name"] for m in bench["end_to_end"]] == [
        "train_step_ms", "setup_s", TPOT]
    m = next(m for m in bench["end_to_end"] if m["name"] == TPOT)
    assert m["workloads"][0] == CELL
    assert len(m["workloads"]) == len(set(m["workloads"]))
    assert (m["unit"], m["better"], m["source"]) == ("ms", "lower",
                                                     "host_clock")
    assert 0.01 <= m["bound"] <= 0.1
    train = next(m for m in bench["end_to_end"]
                 if m["name"] == "train_step_ms")
    assert not set(train["workloads"]) & set(m["workloads"])
    for name in m["workloads"]:
        with open(os.path.join(root, "perf", "workloads",
                               name + ".json")) as f:
            body = json.load(f)
        assert "engine" in body and body["traffic"]["rate_rps"] > 0, name
        drv = load(os.path.join(root, "perf", "drivers",
                                body["driver"] + ".py"),
                   name="perf_test_manifest_driver_" + body["driver"])
        for hook in ("window", "check"):
            assert getattr(getattr(drv, hook), "__module__", None) == (
                "perf.serve_window"), (name, hook)
    for w in bench["workloads"]:
        with open(os.path.join(root, "perf", "workloads",
                               w["name"] + ".json")) as f:
            if "engine" in json.load(f):
                assert w["name"] not in train["workloads"], w["name"]


@pytest.mark.parametrize("name,unit,better,source,layer,moves", PR33,
                         ids=[m[0] for m in PR33])
def test_a_serving_metric_has_its_entry_and_its_reader(
        bench, name, unit, better, source, layer, moves):
    """Each reader as the chat cell brought it but for its cells: the chat
    cell and any serving cell that appended its name. The copy share reads a
    cache gather the engine no longer makes (PERF.md 7 row 32 (a)) and stays
    the chat cell's alone."""
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert dict(entry, workloads=None) == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": moves, "workloads": None}
    assert CELL in entry["workloads"]
    assert set(entry["workloads"]) <= set(serving_cells(bench))
    if name == "decode_copy_share.serve":
        assert entry["workloads"] == [CELL]
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


def _held(old, new):
    """``new`` is ``old`` but for cells appended to its ``workloads``."""
    assert dict(new, workloads=None) == dict(old, workloads=None), old["name"]
    assert set(old.get("workloads", ())) <= set(new.get("workloads", ())), (
        old["name"])


def test_nothing_that_stood_before_was_edited(bench):
    """The cells, metrics and bounds of the parent, entry for entry, at the
    head of each list: the serving cell came as additions, and a later
    cell comes as additions too (its name appended to the ``workloads`` of
    metrics that stood before). ``fixtures/benchmark_one_serving_cell.
    json`` is the manifest as it stood when the serving harness was opened
    to any served model."""
    with open(os.path.join(FIXTURES, "benchmark_one_serving_cell.json")) as f:
        before = json.load(f)
    assert [w["name"] for w in bench["workloads"]][:3] == [
        "gpt2_345m.pretrain", "gpt2_345m.pretrain_dp4",
        "joyai_llm_flash.pretrain"]
    assert bench["workloads"][:len(before["workloads"])] == (
        before["workloads"])
    assert bench["configs"][:len(before["configs"])] == before["configs"]
    train = bench["end_to_end"][0]
    _held({"name": "train_step_ms", "unit": "ms", "better": "lower",
           "bound": 0.01, "source": "host_clock",
           "workloads": ["gpt2_345m.pretrain", "gpt2_345m.pretrain_dp4",
                         "joyai_llm_flash.pretrain"]}, train)
    assert bench["end_to_end"][1] == {
        "name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
        "source": "host_clock"}
    for old, new in zip(before["end_to_end"], bench["end_to_end"]):
        _held(old, new)
    assert len(bench["per_layer"]) >= 17 + len(PR33)
    assert len(before["per_layer"]) == 17 + len(PR33)
    for old, new in zip(before["per_layer"], bench["per_layer"]):
        _held(old, new)
    assert all(not set(serving_cells(bench)) & set(m["workloads"])
               for m in bench["per_layer"][:17])
    assert bench["run_seconds"] == 50 and bench["paths"] == ["perf",
                                                             "tests/perf"]
