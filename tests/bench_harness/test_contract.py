"""``BENCHMARK.json`` against the benchmark's files and the contract's
limits: a later PR that adds a cell, a configuration or a metric as files of
its own has to keep all of this true."""

import importlib
import json
import os
import re

import pytest

from benchmark import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells(bench):
    return [w["name"] for w in bench["workloads"]]


def test_exactly_the_contracts_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, cells // 4)


def test_names_units_and_lines(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry and group != "end_to_end":
                    text = entry[key]
                    assert 1 <= len(text) <= 200 and "\n" not in text \
                        and "\t" not in text, (entry["name"], key)
    assert len(set(names)) == len(names)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in bench["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in bench["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_every_configuration_has_its_file_and_a_cell(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert config["name"] in used
        assert any(config["file"].startswith(p + "/") for p in bench["paths"])
        assert config["file"] not in files
        files.add(config["file"])
        with open(os.path.join(ROOT, config["file"])) as f:
            body = json.load(f)
        assert body["name"] == config["name"]
        assert len(config["reduced"]) <= 16
        for key in config["reduced"]:
            assert NAME.match(key) and not key.endswith(("_dim", "_rank"))


def test_every_cell_has_its_files_kind_and_metrics(bench):
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end_to_end and "workloads" not in end_to_end["setup_s"]
    for cell in _cells(bench):
        spec = harness.load_spec(cell)
        kind = harness.load_kind(spec.workload["kind"])
        for fn in ("build", "describe", "step", "work", "release",
                   "verify", "control", "FAULTS"):
            assert hasattr(kind, fn), (spec.workload["kind"], fn)
        reported = harness.end_to_end_names(spec)
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.workload["metric"] in reported
        assert spec.layer_metrics, cell
        assert set(spec.workload["limits"]), cell


def test_every_layer_metric_has_its_file_reader_and_cells(bench):
    cells = set(_cells(bench))
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    layers = {}
    for metric in bench["per_layer"]:
        path = os.path.join(ROOT, "benchmark", "layer_metrics",
                            metric["name"] + ".json")
        with open(path) as f:
            body = json.load(f)
        assert set(body["workloads"]) <= set(metric["workloads"])
        for key in ("name", "layer", "unit", "source", "moves"):
            assert body[key] == metric[key], (metric["name"], key)
        reader = importlib.import_module(
            "benchmark.readers." + body["reader"])
        assert callable(reader.read)
        moved = end_to_end[metric["moves"]]
        for cell in metric["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells), (
                metric["name"], "moves a metric", cell, "does not report")
        layers.setdefault(metric["layer"].lower(), set()).add(metric["layer"])
        if "roofline" in metric["name"] or "mfu" in metric["name"]:
            assert metric["unit"] == "%"
    assert all(len(spellings) == 1 for spellings in layers.values())
    roofed = {m["moves"] for m in bench["per_layer"]
              if "roofline" in m["name"]}
    with_mfu = {m["moves"] for m in bench["per_layer"]
                if "mfu" in re.split(r"[._\-]", m["name"])}
    assert roofed <= with_mfu


def test_the_paths_hold_the_benchmark_and_only_allowed_file_names(bench):
    assert 1 <= len(bench["paths"]) <= 16
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in bench["paths"]:
        assert allowed.match(path) and len(path) <= 200
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in folder:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert allowed.match(rel), rel
    for word in bench["command"]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in bench["paths"])
