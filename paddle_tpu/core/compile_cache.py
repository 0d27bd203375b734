"""JAX's persistent compilation cache, placed from outside.

One rule for every entry point that compiles at real sizes (chip_smoke.py,
benchmark/run.py, bench_serving.py, the serving worker, the inference
Predictor):
where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and no code
sets another directory; where it is not, the cache lives at ONE fixed path
inside the checkout. The path is part of how a later process finds an
entry, so it is never derived from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def enable(directory=None) -> str:
    """Turn the persistent compilation cache on for this process and return
    the directory in use: ``$JAX_COMPILATION_CACHE_DIR`` when set (left
    exactly as JAX read it), else `directory` (the inference config's
    ``set_optim_cache_dir``), else :data:`DEFAULT_DIR`. Every compile is
    cached, however small or quick. Whether the cache is consulted at all
    stays with ``jax_enable_compilation_cache`` — tests/conftest.py turns
    that off for the children tier-1 tests start."""
    import jax

    from_env = os.environ.get(ENV_VAR)
    if from_env:
        path = from_env
    else:
        path = os.path.abspath(directory or DEFAULT_DIR)
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
