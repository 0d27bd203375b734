"""`--rehearse` end to end: every cell's own code on the CPU at the
`tiny` sizes (the four-chip cell on 4 virtual devices), with and without
the traced window. A rehearsal prints no metric."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest as mf

CELLS = [w["name"] for w in mf.load()["workloads"]]


def rehearse(root, cell, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3", "--seconds", "1",
         "--rehearse", *extra],
        capture_output=True, text=True, timeout=900, env=env, cwd=root,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell):
    line = rehearse(mf.ROOT, cell)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, line["rehearsal"]["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    chips = next(w["chips"] for w in mf.load()["workloads"]
                 if w["name"] == cell)
    assert line["device"]["count"] == chips
    assert "setup_s" in line["rehearsal"]["reported"]


@pytest.mark.parametrize("cell", ["bert_base_mlm_train",
                                  "gpt2_small_generate_closed"])
def test_traced_rehearsal_reads_spans(cell):
    line = rehearse(mf.ROOT, cell, "--trace", "1")
    assert line["correct"] is True
    reported = line["rehearsal"]["reported"]
    assert "compile_s" in reported
    if cell.startswith("gpt2"):
        assert {"queue_wait_ms_p50", "prefill_ms_p50",
                "decode_gap_ms_p50"} <= set(reported)
    else:
        assert "executor_host_ms" in reported


def test_measuring_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=mf.ROOT,
    )
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
