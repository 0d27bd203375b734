"""C inference API: build libpaddle_tpu_capi.so, compile a real C driver
against paddle_tpu_capi.h, run it in a subprocess against a saved model,
and compare its output with the Python predictor (reference
inference/capi tests pattern)."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.framework import unique_name
from paddle_tpu.inference_capi import build_capi, header_path

pytestmark = pytest.mark.skipif(
    shutil.which("gcc") is None or shutil.which("g++") is None,
    reason="no C/C++ toolchain",
)

_DRIVER = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include "paddle_tpu_capi.h"

int main(int argc, char** argv) {
  PD_AnalysisConfig* cfg = PD_NewAnalysisConfig();
  PD_SetModel(cfg, argv[1], NULL, NULL);
  PD_Predictor* pred = PD_NewPredictor(cfg);
  if (!pred) {
    fprintf(stderr, "predictor: %s\n", PD_GetLastError());
    return 2;
  }
  printf("inputs=%d outputs=%d in0=%s out0=%s\n", PD_GetInputNum(pred),
         PD_GetOutputNum(pred), PD_GetInputName(pred, 0),
         PD_GetOutputName(pred, 0));

  float data[4 * 8];
  for (int i = 0; i < 32; i++) data[i] = (float)i / 31.0f - 0.5f;
  int64_t shape[2] = {4, 8};
  PD_TensorC in = {PD_GetInputName(pred, 0), PD_FLOAT32, shape, 2, data,
                   sizeof(data)};
  PD_TensorC* outs = NULL;
  int n_out = 0;
  if (!PD_PredictorRun(pred, &in, 1, &outs, &n_out)) {
    fprintf(stderr, "run: %s\n", PD_GetLastError());
    return 3;
  }
  printf("n_out=%d rank=%d dtype=%d bytes=%zu\n", n_out, outs[0].rank,
         outs[0].dtype, outs[0].byte_size);
  const float* y = (const float*)outs[0].data;
  size_t n = outs[0].byte_size / sizeof(float);
  for (size_t i = 0; i < n; i++) printf("%.6f\n", y[i]);
  PD_FreeOutputs(outs, n_out);

  /* zero-copy run: output data points into predictor-owned buffers */
  PD_TensorC* zouts = NULL;
  int zn = 0;
  if (!PD_ZeroCopyRun(pred, &in, 1, &zouts, &zn)) {
    fprintf(stderr, "zrun: %s\n", PD_GetLastError());
    return 4;
  }
  printf("zero_copy n=%d\n", zn);
  {
    const float* zy = (const float*)zouts[0].data;
    size_t zn_el = zouts[0].byte_size / sizeof(float);
    for (size_t i = 0; i < zn_el; i++) printf("%.6f\n", zy[i]);
  }
  PD_FreeZeroCopyOutputs(zouts, zn);
  PD_DeletePredictor(pred);
  PD_DeleteAnalysisConfig(cfg);
  return 0;
}
"""


def test_c_api_end_to_end(tmp_path):
    # ---- save a small model + compute the Python-side reference ----
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 4
    scope = fluid.framework.scope.Scope()
    feed = (np.arange(32, dtype=np.float32) / 31.0 - 0.5).reshape(4, 8)
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        x = fluid.data("x", [4, 8])
        y = layers.fc(x, 5, act="tanh")
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        model_dir = str(tmp_path / "model")
        fluid.io.save_inference_model(model_dir, ["x"], [y], exe,
                                      main_program=main)
        (ref,) = exe.run(main, feed={"x": feed}, fetch_list=[y], scope=scope)
    ref = np.asarray(ref)

    # ---- build the shared library and the C driver ----
    lib = build_capi()
    driver_c = tmp_path / "driver.c"
    driver_c.write_text(_DRIVER)
    driver = tmp_path / "driver"
    subprocess.run(
        ["gcc", str(driver_c), "-o", str(driver),
         f"-I{os.path.dirname(header_path())}", str(lib),
         f"-Wl,-rpath,{os.path.dirname(lib)}"],
        check=True, capture_output=True, text=True,
    )

    # ---- run the C program; the embedded interpreter must see our repo
    # and run jax on CPU (no conftest inside the C process) ----
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the embedded interpreter must run jax on the CPU so the comparison
    # against the (CPU) pytest reference is exact
    keep = [
        p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p
    ]
    env["PYTHONPATH"] = os.pathsep.join([repo, *keep])
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [str(driver), model_dir], capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("inputs=1 outputs=1 in0=x")
    meta = lines[1]
    assert "n_out=1" in meta and "rank=2" in meta and "dtype=0" in meta
    zc = lines.index("zero_copy n=1")
    got = np.array([float(v) for v in lines[2:zc]], np.float32).reshape(4, 5)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    # zero-copy outputs read in place from predictor-owned buffers
    zgot = np.array([float(v) for v in lines[zc + 1:]],
                    np.float32).reshape(4, 5)
    np.testing.assert_allclose(zgot, ref, rtol=1e-5, atol=1e-6)
