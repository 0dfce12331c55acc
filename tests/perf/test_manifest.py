"""BENCHMARK.json against the contract's rules of form, and against the
files it names. Each rule is a function of the manifest's dict (and of the
root its files lie under), so that ``test_manifest_room.py`` can hold a
manifest with a cell more to the same rules."""

import json
import os
import re

import pytest

from _bench import REPO, benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return benchmark()


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_parses_with_exactly_the_contracts_keys(bench, root=REPO):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(root, p))


def test_command_names_a_file_under_paths(bench, root=REPO):
    files = [w for w in bench["command"] if "/" in w]
    assert files, "the command names no program file"
    for w in files:
        assert any(w.startswith(p + "/") for p in bench["paths"])
        assert os.path.isfile(os.path.join(root, w))


def test_names_units_and_lengths(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    for group in (metrics, bench["workloads"], bench["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_config_is_used_and_its_file_states_its_sizes(bench,
                                                           root=REPO):
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(root, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head_)",
                                 key), f"{key} is a width"


def test_every_cells_files_exist(bench, root=REPO):
    perf = os.path.join(root, "perf")
    for w in bench["workloads"]:
        path = os.path.join(perf, "workloads", w["name"] + ".json")
        assert os.path.isfile(path), path
        with open(path) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"]
        assert cell["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(perf, "drivers",
                                           cell["driver"] + ".py"))
        assert "limits" in cell and cell["limits"]
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(perf, "layer_metrics",
                                           m["name"] + ".py")), m["name"]


def test_every_cell_reports_what_its_layer_metrics_move(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def reported_by(metric):
        return set(metric.get("workloads", cells))

    for m in bench["end_to_end"] + bench["per_layer"]:
        assert reported_by(m) <= cells, m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        target = reported_by(e2e[m["moves"]])
        if "workloads" in m:
            assert set(m["workloads"]) <= target, m["name"]
    for cell in cells:
        mine = [n for n, m in e2e.items() if cell in reported_by(m)]
        assert "setup_s" in mine and len(mine) >= 2, cell
        layer = [m for m in bench["per_layer"]
                 if cell in reported_by(m) and m["moves"] in mine]
        assert layer, f"{cell} reports no per-layer metric"
        # beside a kernel's roofline, the whole step's share of the peak
        for m in layer:
            if m["name"].endswith("_roofline"):
                assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                           for o in layer), m["name"]


def test_layer_names_are_perf_mds(bench, root=REPO):
    with open(os.path.join(root, "PERF.md")) as f:
        text = f.read()
    for m in bench["per_layer"]:
        assert m["layer"] in text, m["layer"]


#: the per-layer metrics PR 32 added: (name, unit, better, source, layer,
#: cells); every one moves train_step_ms and is reported by at least those
#: cells (a later cell may join it)
ALL = ["gpt2_345m.pretrain", "gpt2_345m.pretrain_dp4",
       "joyai_llm_flash.pretrain"]
JOYAI = ["joyai_llm_flash.pretrain"]
PR32 = [
    ("step_forward_ms.train", "ms", "lower", "device_trace", "step builder",
     ALL),
    ("step_backward_ms.train", "ms", "lower", "device_trace", "step builder",
     ALL),
    ("step_optimizer_ms.train", "ms", "lower", "device_trace",
     "step builder", ALL),
    ("step_guard_ms.train", "ms", "lower", "device_trace", "step builder",
     ALL),
    ("step_grad_sync_ms.train", "ms", "lower", "device_trace", "parallel",
     ["gpt2_345m.pretrain_dp4"]),
    ("flash_fwd_roofline", "%", "higher", "device_trace", "kernels", ALL),
    ("flash_bwd_dq_roofline", "%", "higher", "device_trace", "kernels", ALL),
    ("flash_bwd_dkv_roofline", "%", "higher", "device_trace", "kernels",
     ALL),
    ("moe_routed_path_ms.train", "ms", "lower", "device_trace", "model",
     JOYAI),
    ("mla_project_ms.train", "ms", "lower", "device_trace", "model", JOYAI),
    ("moe_compact_share.train", "share", "higher", "program_counter",
     "model", JOYAI),
]


@pytest.mark.parametrize("name,unit,better,source,layer,cells", PR32,
                         ids=[m[0] for m in PR32])
def test_a_new_metric_has_its_entry_and_its_reader(bench, name, unit, better,
                                                   source, layer, cells):
    from _bench import load

    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert dict(entry, workloads=None) == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "train_step_ms", "workloads": None}
    assert set(cells) <= set(entry["workloads"])
    reader = load(f"layer_metrics/{name}.py")
    assert callable(reader.read) and reader.__doc__
    # a reader that finds nothing to read returns nothing, never 0
    import types

    assert reader.read(types.SimpleNamespace(
        reduction=None, counters={}, peaks=None, chips=1,
        config={"n_layer": 2, "n_head": 4, "n_embd": 64},
        cell={"global_batch": 4, "seq_len": 32},
        device={"platform": "cpu"})) is None


def test_the_metrics_that_stood_before_still_stand(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:6] == ["mfu_pct.train", "device_idle_pct.train",
                         "flash_attn_roofline", "mla_attn_roofline",
                         "moe_experts_roofline",
                         "moe_load_max_over_mean.train"]
    assert [m["name"] for m in bench["end_to_end"]][:2] == ["train_step_ms",
                                                           "setup_s"]
    assert [m["bound"] for m in bench["end_to_end"]][:2] == [0.01, 0.1]
    assert bench["run_seconds"] == 50
