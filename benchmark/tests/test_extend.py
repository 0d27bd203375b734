"""A later PR adds a cell, a configuration and a per-layer metric by
adding files and manifest entries, and edits no file that is there."""

import hashlib
import json
import os
import shutil

from benchmark.harness import manifest as mf

from .test_rehearse import rehearse


def _digests(root):
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()
                ).hexdigest()
    return out


def test_a_fifth_cell_is_files_and_entries_only(tmp_path):
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copytree(os.path.join(mf.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(mf.ROOT, "paddle_tpu"),
               os.path.join(root, "paddle_tpu"))
    before = _digests(root)

    # a configuration: BERT-base's file under another name
    cfg = mf.config(mf.load(), "bert_base")
    cfg["assumed"]["dummy"] = "a copy, to show the harness needs no edit"
    with open(os.path.join(root, "benchmark/configs/dummy.json"), "w") as f:
        json.dump(cfg, f)
    # a cell: the BERT traffic kind at other parameters
    _entry, cell = mf.cell(mf.load(), "bert_base_mlm_train")
    cell["config"] = "dummy"
    cell["traffic"].update(name="dummy_train", batch=32)
    cell["rehearse"].update(batch=2)
    with open(os.path.join(root, "benchmark/workloads/dummy_cell.json"),
              "w") as f:
        json.dump(cell, f)
    # a per-layer metric: a reader of its own
    with open(os.path.join(root, "benchmark/layer_metrics/dummy_steps.py"),
              "w") as f:
        f.write("def read(run):\n    return run.facts['steps']\n")

    manifest = mf.load()
    manifest["configs"].append({
        "name": "dummy", "source": cfg["source"],
        "file": "benchmark/configs/dummy.json", "reduced": [],
        "why": "dummy"})
    manifest["workloads"].append({
        "name": "dummy_cell", "config": "dummy", "traffic": "dummy_train",
        "chips": 1, "why": "dummy"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "bert_base_mlm_train" in m.get("workloads", []):
            m["workloads"].append("dummy_cell")
    manifest["per_layer"].append({
        "name": "dummy_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "executor",
        "moves": "train_tokens_per_s", "workloads": ["dummy_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    line = rehearse(root, "dummy_cell", "--trace", "1")
    assert line["correct"] is True
    assert "dummy_steps" in line["rehearsal"]["reported"]
    after = _digests(root)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "benchmark/configs/dummy.json",
        "benchmark/workloads/dummy_cell.json",
        "benchmark/layer_metrics/dummy_steps.py",
    }
