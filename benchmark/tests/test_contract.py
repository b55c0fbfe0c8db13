"""BENCHMARK.json against the contract's limits on names, units and files, and
against the files it names."""
import json
import os
import re

import pytest

from harness import loader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return loader.bench_spec()


def test_keys_and_sizes(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(loader.REPO_DIR, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert 1 <= len(spec["workloads"]) <= 24 and 1 <= len(spec["configs"]) <= 24
    # a full check fits its limit with all 24 cells
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_and_units(spec):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in spec[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
    assert len(set(names)) == len(names)
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_cells_and_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    used = set()
    cells = {w["name"] for w in spec["workloads"]}
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, len(cells) // 4)
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        cell = loader.load_json("workloads", w["name"])
        assert not set(cell) & set(w)  # each key of a cell stands in one place
        assert loader.resolve_cell(w["name"])["chips"] == w["chips"]
        loader.load_json("traffic", w["traffic"])
        loader.load_module("runners", cell["runner"])
        for metric in cell["layer_metrics"]:
            assert any(m["name"] == metric for m in spec["per_layer"]), metric
    assert used == set(configs)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(spec["paths"][0] + "/")
        with open(os.path.join(loader.REPO_DIR, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for family_file in ("models", "references", "flops"):
            loader.load_module(family_file, body["family"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        for cell in m.get("workloads", []):
            assert cell in cells


def test_layer_metric_readers_say_what_the_file_says(spec):
    for m in spec["per_layer"]:
        reader = loader.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == \
            (m["name"], m["unit"], m["layer"], m["moves"], m["source"])
        assert reader.read({}) is None  # nothing to read: nothing returned


def test_files_under_paths_are_named_from_a_names_characters(spec):
    for root in spec["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(loader.REPO_DIR, root)):
            dirnames[:] = [d for d in dirnames if d not in (".cache", "__pycache__")]
            for f in filenames:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(dirpath, f)
