"""`BENCHMARK.json` and the files it names.

A cell `<name>` has `benchmark/workloads/<name>.json` (its traffic kind
and parameters, what to expect of the compiled step, and the overlay a
rehearsal uses); a configuration `<name>` has
`benchmark/configs/<name>.json`; a traffic kind `<kind>` has the driver
`benchmark/traffic/<kind>.py`; a family has the builder
`benchmark/builders/<family>.py`; a per-layer metric `<name>` has the
reader `benchmark/layer_metrics/<name>.py`. Adding one of any of these is
adding a file and a manifest entry.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def cell(manifest, name):
    """(the manifest's entry, the cell's file)."""
    for entry in manifest["workloads"]:
        if entry["name"] == name:
            return entry, _read_json("workloads", f"{name}.json")
    known = [w["name"] for w in manifest["workloads"]]
    raise SystemExit(f"benchmark: no cell {name!r} in BENCHMARK.json "
                     f"(cells: {known})")


def config(manifest, name):
    for entry in manifest["configs"]:
        if entry["name"] == name:
            with open(os.path.join(ROOT, entry["file"])) as f:
                return json.load(f)
    raise SystemExit(f"benchmark: no configuration {name!r}")


def metrics_of(manifest, section, cell_name):
    """The entries of `end_to_end` or `per_layer` this cell reports."""
    return [m for m in manifest[section]
            if cell_name in m.get("workloads", [cell_name])]


def driver(kind):
    return importlib.import_module(f"benchmark.traffic.{kind}")


def reader(metric_name):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric_name}"
    ).read
