"""Native C++ training demo: build the embedded-CPython trainer, save a
training bundle, run the binary in a subprocess, and assert the loss it
prints decreases (reference train/demo/demo_trainer.cc end-to-end)."""

import os
import shutil
import subprocess

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.framework import unique_name
from paddle_tpu.train_demo import build_demo, save_train_bundle

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no C++ toolchain"
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cpp_train_demo(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [16, 4])
        y = fluid.data("y", [16, 1])
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.05).minimize(loss, startup)
    rng = np.random.RandomState(0)
    xv = rng.randn(16, 4).astype(np.float32)
    yv = (xv @ np.arange(4, dtype=np.float32).reshape(4, 1))
    bundle = str(tmp_path / "bundle.pkl")
    save_train_bundle(bundle, main, startup, {"x": xv, "y": yv}, loss.name)

    binary = build_demo()
    env = dict(os.environ)
    keep = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO, *keep])
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([binary, bundle, "8"], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [l for l in proc.stdout.splitlines() if l.startswith("step ")]
    assert len(lines) == 8
    losses = [float(l.split()[-1]) for l in lines]
    assert losses[-1] < losses[0]
    assert "done" in proc.stdout
