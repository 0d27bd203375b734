"""The bring-up contract (ISSUE 21): nothing on the chip path passes
without the chip, and nothing hides a failed leg.

* ``chip_smoke.py`` on the CPU exits non-zero with ``"ok": false``;
* ``TPUPlace(0).jax_device()`` raises on a CPU-only process while
  ``default_place()`` still serves tests;
* the compile-cache helper honours ``JAX_COMPILATION_CACHE_DIR`` and
  otherwise names one fixed path inside the checkout;
* ``bench_serving.py`` exits non-zero when a mix raises and still prints
  the others;
* (slow) every chip_smoke phase runs at a tiny size on the CPU — the
  rehearsal to make before spending chip time.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # bench_serving.py lives at the root
    sys.path.insert(0, REPO)


def _last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert proc.returncode not in (0, None), proc.stdout + proc.stderr
    last = _last_json(proc.stdout)
    assert last["ok"] is False
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    # it stopped before building anything: no phase line was printed
    assert '"phase"' not in proc.stdout


def test_tpu_place_never_resolves_to_a_cpu_device():
    import paddle_tpu as fluid
    from paddle_tpu.core.place import default_place
    from paddle_tpu.errors import UnavailableError

    with pytest.raises(UnavailableError, match="no tpu device"):
        fluid.TPUPlace(0).jax_device()
    with pytest.raises(UnavailableError):
        fluid.Executor(fluid.TPUPlace(0))
    assert fluid.tpu_places() == []
    place = default_place()
    assert isinstance(place, fluid.CPUPlace)
    assert place.jax_device().platform == "cpu"
    fluid.Executor()  # the default place resolves


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path,
                                              compile_cache_settings):
    from paddle_tpu.core import compile_cache

    # the default: one fixed git-ignored path inside the checkout
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first, second = compile_cache.enable(), compile_cache.enable()
    assert first == second == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR

    # inference.AnalysisConfig.set_optim_cache_dir's directory
    user = str(tmp_path / "user")
    assert compile_cache.enable(user) == user
    assert jax.config.jax_compilation_cache_dir == user

    # placed from outside: returned as is, and no code path sets another
    outside = str(tmp_path / "outside")
    monkeypatch.setenv(compile_cache.ENV_VAR, outside)
    assert compile_cache.enable() == outside
    assert compile_cache.enable(str(tmp_path / "other")) == outside
    assert jax.config.jax_compilation_cache_dir == user  # untouched
    assert not os.path.exists(tmp_path / "other")


def test_bench_serving_main_exits_nonzero_when_a_mix_raises(monkeypatch,
                                                            capsys):
    import bench_serving
    from paddle_tpu.core import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda *a: "")

    def boom(*a, **k):
        raise RuntimeError("mix blew up")

    def gpt(smoke, results):
        results["gpt_generate"] = {"qps": 1.0}
        return {"kv_decode_speedup": 9.0, "kv_parity": True}

    monkeypatch.setattr(bench_serving, "bench_classify_mix", boom)
    monkeypatch.setattr(bench_serving, "bench_gpt_generate", gpt)
    assert bench_serving.main(["--smoke", "--mix", "bert,gpt"]) == 1
    summary = _last_json(capsys.readouterr().out)
    assert summary["raised"] == ["bert"]
    assert summary["kv_parity"] is True  # the gpt mix still ran
    assert bench_serving.main(["--smoke", "--mix", "gpt"]) == 0


_REHEARSAL = r"""
import json, sys
import chip_smoke as cs

TINY = cs.Sizes(
    bert="tiny", bert_batch=4, bert_seq=64, train_steps=8,
    gpt="tiny", long_batch=2, long_seq=128,
    serve_seq=16, serve_buckets=(1, 2, 4, 8),
    gen_context=32, gen_new=6,
    moe="tiny", moe_batch=4, moe_context=24, moe_max_len=40, moe_new=9,
    ring_batch=1, ring_heads=8, ring_seq=64, ring_head_dim=16,
)
phases = cs.FOUR_CHIP_PHASES if sys.argv[1] == "4" else cs.ONE_CHIP_PHASES
meter, shared = cs.CompileMeter(), {}
# kernels=False: on the CPU every dispatch takes the jnp path, so each
# phase expects zero tpu_custom_call in its HLO
ok = [cs.run_phase(n, fn, meter, TINY, False, shared) for n, fn in phases]
sys.exit(0 if all(ok) else 1)
"""


@pytest.mark.slow
@pytest.mark.parametrize("chips", ["1", "4"])
def test_phases_rehearse_on_cpu(chips):
    """on-chip-measurement guide, section 2, rehearsals 1 and 2: the same
    phase code at a tiny size on the CPU; the four-chip legs on 4 virtual
    devices (a child, because the device count is fixed at start-up)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _REHEARSAL, chips],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1500,
    )
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith('{"phase"')]
    assert proc.returncode == 0, (lines, proc.stderr[-3000:])
    assert all(l["ok"] for l in lines)
    assert len(lines) == (2 if chips == "4" else 5)
