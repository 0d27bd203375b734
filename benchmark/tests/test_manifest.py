"""`BENCHMARK.json` against the files it names and the contract's
limits."""

import importlib
import json
import os
import re

import pytest

from benchmark.harness import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return mf.load()


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["paths"] == ["benchmark"]
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) < 65536


def test_names_units_and_lines(manifest):
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[section]]
        assert len(names) == len(set(names)), section
        for e in manifest[section]:
            assert NAME.match(e["name"]), e["name"]
    metric_names = [m["name"] for m in manifest["end_to_end"]
                    + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\t" not in w["why"]
        assert w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200


def test_cells_configs_and_chips(manifest):
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))


def test_every_name_resolves_to_a_file(manifest):
    for c in manifest["configs"]:
        assert PATH.match(c["file"]) and c["file"].startswith("benchmark/")
        cfg = mf.config(manifest, c["name"])
        assert cfg["reduced"] == c["reduced"]
        importlib.import_module(f"benchmark.builders.{cfg['builder']}")
    for w in manifest["workloads"]:
        entry, cell = mf.cell(manifest, w["name"])
        assert cell["config"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
        assert hasattr(mf.driver(cell["traffic"]["kind"]), "run")
    for m in manifest["per_layer"]:
        assert callable(mf.reader(m["name"]))


def test_metrics_and_cells_agree(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in manifest["end_to_end"]}
    assert e2e["setup_s"] == set(cells)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells), m["name"]
    for m in manifest["per_layer"]:
        # a per-layer metric is reported only where the metric it moves is
        assert m["moves"] in e2e, m
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]
    for cell in cells:
        reported = [n for n, where in e2e.items() if cell in where]
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert mf.metrics_of(manifest, "per_layer", cell), cell
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_files_under_paths_have_plain_names():
    for base, _dirs, files in os.walk(mf.BENCH_DIR):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), mf.ROOT)
            assert PATH.match(rel), rel


def test_config_files_state_their_source(manifest):
    for c in manifest["configs"]:
        with open(os.path.join(mf.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert "assumed" in cfg and "tiny" in cfg
