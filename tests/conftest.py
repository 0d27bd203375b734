"""Test config: force an 8-device virtual CPU mesh so multi-chip sharding
paths run without TPU hardware (mirrors the reference's strategy of testing
distributed modes on localhost, test_dist_base.py:506). Tests run on the
CPU whatever the host has: the platform is pinned below as well as by the
tier-1 command's JAX_PLATFORMS=cpu.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Set AFTER this process imported jax, so it reaches the children tests
# start (serving workers, launcher ranks, bench drivers: those call
# core.compile_cache.enable()) and, under xdist, the workers themselves,
# which import jax after the controller ran this line: a tier-1 run must
# neither write a persistent compile cache into the checkout nor read a
# stale one. A test of the cache turns it on for itself.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import copy  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def compile_cache_settings():
    """Snapshot/restore the process-global persistent-compile-cache
    settings and JAX's handle on the directory, for a test that places or
    enables the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        yield saved
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (tier-1 verify runs -m 'not slow')"
    )


@pytest.fixture(autouse=True)
def _isolate_global_state():
    """Snapshot/restore every piece of process-global framework state so a
    test that mutates flags, the active mesh, the current scope, or the
    default programs cannot leak into later tests (order-dependent failures,
    e.g. the round-2 test_compiled_program_data_parallel_runs flake)."""
    from paddle_tpu import flags as _flags
    from paddle_tpu.framework import program as _prog
    from paddle_tpu.framework import scope as _scope
    from paddle_tpu.framework import unique_name as _un
    from paddle_tpu.observability import metrics as _met
    from paddle_tpu.observability import spans as _spans
    from paddle_tpu.parallel import mesh as _mesh
    from paddle_tpu.resilience import faults as _faults

    saved_metrics = copy.deepcopy(
        (_met._counters, _met._gauges, _met._histograms, _met._tables)
    )
    saved_enabled = _met._enabled
    saved_spans = list(_spans._spans)
    saved_flags = copy.deepcopy(_flags._FLAGS)
    saved_mesh = _mesh._current_mesh
    saved_scope = _scope._current_scope
    saved_main = _prog._main_program
    saved_startup = _prog._startup_program
    saved_device = _prog._current_device
    saved_gen = _un._generator
    saved_faults = (dict(_faults._registry), _faults._env_loaded)
    try:
        yield
    finally:
        for store, saved in zip(
            (_met._counters, _met._gauges, _met._histograms, _met._tables),
            saved_metrics,
        ):
            store.clear()
            store.update(saved)
        _met._enabled = saved_enabled
        _spans._spans.clear()
        _spans._spans.extend(saved_spans)
        _flags._FLAGS.clear()
        _flags._FLAGS.update(saved_flags)
        _mesh._current_mesh = saved_mesh
        _scope._current_scope = saved_scope
        _prog._main_program = saved_main
        _prog._startup_program = saved_startup
        _prog._current_device = saved_device
        _un._generator = saved_gen
        _faults._registry.clear()
        _faults._registry.update(saved_faults[0])
        _faults._env_loaded = saved_faults[1]
