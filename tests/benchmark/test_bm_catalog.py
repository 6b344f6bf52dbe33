"""``BENCHMARK.json`` against its contract, and against the files it names."""

import importlib
import os
import re

import pytest

import bm_helpers

BENCH = bm_helpers.load("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
REPLAY_CELLS = {"criteo_ftrl.replay_uniform", "criteo_fm.replay_uniform"}
STREAM_CELLS = {"criteo_ftrl.stream_fields"}


def _cells():
    return {c["name"]: c for c in BENCH["workloads"]}


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(bm_helpers.REPO,
                                        "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_single_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            names.append((group, entry["name"]))
            assert NAME.match(entry["name"]), entry["name"]
            lines = [entry[k] for k in ("why", "layer") if k in entry]
            if group == "configs":
                lines.append(entry["source"])
            for text in lines:
                assert 1 <= len(text) <= 200
                assert "\n" not in text and "\t" not in text
    assert len(set(names)) == len(names)
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_entries_have_just_the_contracts_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_configs_files_and_cuts():
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        cfg = bm_helpers.load(c["file"])
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
        assert cfg["nnz"] == 39 and cfg["block_rows"] == 98304   # widths
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)


def test_cells_name_files_that_exist():
    pairs = set()
    for w in BENCH["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert any(c["name"] == w["config"] for c in BENCH["configs"])
        traffic = bm_helpers.load(f"benchmark/traffic/{w['traffic']}.json")
        assert traffic["name"] == w["traffic"]
        assert traffic["regime"] in ("replay", "stream")
        importlib.import_module(traffic["generator"])
        for part in ("system", "reference", "roofline"):
            importlib.import_module(
                f"benchmark.configs.{w['config']}.{part}")
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_two_rates_for_two_regimes():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert set(e2e["train_ex_per_s"]["workloads"]) == REPLAY_CELLS & set(
        _cells())
    assert set(e2e["stream_ex_per_s"]["workloads"]) == STREAM_CELLS & set(
        _cells())
    assert "workloads" not in e2e["setup_s"]
    for name, cell in _cells().items():
        regime = bm_helpers.load(
            f"benchmark/traffic/{cell['traffic']}.json")["regime"]
        rate = {"replay": "train_ex_per_s", "stream": "stream_ex_per_s"}
        reported = {m["name"] for m in BENCH["end_to_end"]
                    if _reports(m, name)}
        assert reported == {rate[regime], "setup_s"}


def test_every_per_layer_metric_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert cell in _cells()
            assert _reports(e2e[m["moves"]], cell), (m["name"], cell)
    for name in _cells():
        assert any(_reports(m, name) for m in BENCH["per_layer"])


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers == {"pass loop", "feed", "step", "kernel", "device"}
    perf = open(os.path.join(bm_helpers.REPO, "PERF.md")).read()
    for layer in layers:
        assert f"**{layer}**" in perf, layer


@pytest.mark.parametrize(
    "metric", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_its_file_and_reader(metric):
    from benchmark import run
    spec = bm_helpers.load(f"benchmark/metrics/{metric}.json")
    entry = next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                 if m["name"] == metric)
    assert spec["name"] == metric
    for key in ("unit", "better", "source"):
        assert spec[key] == entry[key], (metric, key)
    if spec["kind"] == "per_layer":
        assert spec["layer"] == entry["layer"]
        assert spec["moves"] == entry["moves"]
        assert metric.endswith("." + spec["regime"])
    assert callable(run.reader_of(bm_helpers.REPO, metric))


def test_a_roofline_share_is_named_and_united_as_one():
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
            assert m["unit"] == "%" and m["source"] == "device_trace"


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for base, dirs, names in os.walk(os.path.join(bm_helpers.REPO, path)):
            dirs[:] = [d for d in dirs
                       if d not in (".cache", "__pycache__")]
            for n in names:
                rel = os.path.relpath(os.path.join(base, n), bm_helpers.REPO)
                assert ok.match(rel), rel
