"""AnalysisConfig knobs that ACT (VERDICT r3 item 5): bf16 inference mode,
batch bucketing, persistent optim cache, AOT executable serialize/reload,
zero-copy run. Reference: inference/api/paddle_analysis_config.h,
analysis_predictor.cc, details/zero_copy_tensor.cc."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.framework import unique_name
from paddle_tpu.inference import (AnalysisConfig, PaddleTensor,
                                  create_paddle_predictor)


def _save_model(tmp_path, batch=4):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    scope = fluid.framework.scope.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        x = fluid.data("x", [batch, 8])
        y = layers.fc(x, 5, act="tanh")
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        model_dir = str(tmp_path / "model")
        fluid.io.save_inference_model(model_dir, ["x"], [y], exe,
                                      main_program=main)
        feed = np.linspace(-0.5, 0.5, batch * 8,
                           dtype=np.float32).reshape(batch, 8)
        (ref,) = exe.run(main, feed={"x": feed}, fetch_list=[y],
                         scope=scope)
    return model_dir, feed, np.asarray(ref)


def test_bf16_mode_rewrites_and_runs(tmp_path):
    model_dir, feed, ref = _save_model(tmp_path)
    cfg = AnalysisConfig(model_dir)
    cfg.enable_bf16()
    pred = create_paddle_predictor(cfg)
    # the rewrite must actually insert casts (stub check: VERDICT r3 #5)
    ops = [op.type for op in pred._program.global_block.ops]
    assert "cast" in ops, ops
    (out,) = pred.run([PaddleTensor(feed, "x")])
    got = out.as_ndarray().astype(np.float32)
    # bf16 matmul: ~1e-2 relative agreement with the fp32 reference
    np.testing.assert_allclose(got, ref, rtol=3e-2, atol=3e-2)


def test_batch_bucketing_pads_and_slices(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    scope = fluid.framework.scope.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        x = fluid.data("x", [-1, 8])
        y = layers.fc(x, 5, act="tanh")
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        model_dir = str(tmp_path / "m2")
        fluid.io.save_inference_model(model_dir, ["x"], [y], exe,
                                      main_program=main)
    cfg = AnalysisConfig(model_dir)
    cfg.set_batch_buckets([4, 16])
    pred = create_paddle_predictor(cfg)
    rng = np.random.RandomState(0)
    for b in (1, 3, 4, 7, 16):
        feed = rng.randn(b, 8).astype(np.float32)
        (out,) = pred.run([PaddleTensor(feed, "x")])
        assert out.as_ndarray().shape == (b, 5)
    # only two bucket shapes should have been compiled
    sigs = {k[2] for k in pred._exe._cache}
    batches = {dict((n, s) for n, s, _ in sig)["x"][0] for sig in sigs}
    assert batches <= {4, 16}, batches
    with pytest.raises(Exception, match="largest configured bucket"):
        pred.run([PaddleTensor(rng.randn(32, 8).astype(np.float32), "x")])


@pytest.fixture
def compile_cache_on(monkeypatch, compile_cache_settings):
    """The persistent compile cache on for one test: a tier-1 worker
    starts with it off (the export in tests/conftest.py reaches xdist's
    workers too), and the predictor's note of the directory is
    process-global like JAX's own settings."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu import inference

    monkeypatch.setattr(inference, "_applied_optim_cache_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def test_optim_cache_dir_persists_compiles(tmp_path, compile_cache_on):
    model_dir, feed, ref = _save_model(tmp_path)
    cache = tmp_path / "xla_cache"
    cfg = AnalysisConfig(model_dir)
    cfg.set_optim_cache_dir(str(cache))
    pred = create_paddle_predictor(cfg)
    (out,) = pred.run([PaddleTensor(feed, "x")])
    np.testing.assert_allclose(out.as_ndarray(), ref, rtol=1e-5, atol=1e-6)
    assert cache.exists() and any(cache.iterdir()), (
        "persistent compilation cache produced no entries"
    )


def test_aot_serialize_and_reload(tmp_path):
    """Serialize in this process; reload + serve in a FRESH process (the
    deployment shape: the serving process never invokes XLA compilation).
    XLA:CPU registers compiled-function names process-globally, so
    deserializing into the compiling process is not the supported path —
    cross-process is."""
    import subprocess
    import sys

    model_dir, feed, ref = _save_model(tmp_path)
    cfg = AnalysisConfig(model_dir)
    pred = create_paddle_predictor(cfg)
    aot = str(tmp_path / "model.aotexe")
    pred.save_executable(aot, [PaddleTensor(feed, "x")])
    assert os.path.getsize(aot) > 0

    feed_file = str(tmp_path / "feed.npy")
    np.save(feed_file, feed)
    script = (
        "import os; os.environ.pop('XLA_FLAGS', None)\n"
        "import numpy as np\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from paddle_tpu.inference import (AnalysisConfig, PaddleTensor,\n"
        "                                  create_paddle_predictor)\n"
        f"cfg = AnalysisConfig({model_dir!r})\n"
        f"cfg.set_aot_executable_path({aot!r})\n"
        "pred = create_paddle_predictor(cfg)\n"
        f"feed = np.load({feed_file!r})\n"
        "(out,) = pred.run([PaddleTensor(feed, 'x')])\n"
        "(out2,) = pred.run([PaddleTensor(feed, 'x')])\n"
        "assert np.allclose(out.as_ndarray(), out2.as_ndarray())\n"
        f"np.save({str(tmp_path / 'out.npy')!r}, out.as_ndarray())\n"
        "print('AOT_OK')\n"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # serialized for 1 device, not the 8-dev mesh
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0 and "AOT_OK" in proc.stdout, (
        proc.stdout + proc.stderr
    )
    got = np.load(str(tmp_path / "out.npy"))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_aot_signature_mismatch_raises(tmp_path):
    from paddle_tpu import errors

    model_dir, feed, ref = _save_model(tmp_path)
    cfg = AnalysisConfig(model_dir)
    pred = create_paddle_predictor(cfg)
    aot = str(tmp_path / "model.aotexe")
    pred.save_executable(aot, [PaddleTensor(feed, "x")])
    with pytest.raises(errors.InvalidArgumentError, match="was built for"):
        pred._exe.load_executable(
            aot, pred._program,
            feed={"x": np.zeros((2, 8), np.float32)},
            fetch_list=pred._fetch_vars, scope=pred._scope,
        )


def test_run_zero_copy_returns_predictor_owned_buffers(tmp_path):
    model_dir, feed, ref = _save_model(tmp_path)
    pred = create_paddle_predictor(AnalysisConfig(model_dir))
    names, arrays = pred.run_zero_copy([PaddleTensor(feed, "x")])
    assert names == pred.get_output_names()
    np.testing.assert_allclose(arrays[0], ref, rtol=1e-5, atol=1e-6)
    # buffers are kept alive on the predictor (C API reads them in place)
    assert pred._last_outputs is not None
    assert pred._last_outputs[0].ctypes.data == arrays[0].ctypes.data
