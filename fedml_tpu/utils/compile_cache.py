"""The one persistent XLA compilation cache every entry point shares.

The directory is part of JAX's cache key, so it must never move between
runs: `JAX_COMPILATION_CACHE_DIR`, when set, decides (JAX reads the
variable itself — this module then sets no directory in code);
otherwise the cache lives at `<checkout>/.jax_cache`, a fixed path next
to the package.  Never a temp name, a pid or a timestamp.

Callers: `fedml_tpu.cli.main`, `fedbench/run.py`, `chip_smoke.py`, the
jax-running scripts under `tools/`, and `tests/conftest.py`.  Not the ranks
of a multi-process cluster (`parallel/mh_worker.py`, the tests' multihost
workers): a rank that loads an entry while its peers compile falls out of
step with them.
"""
from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure(min_compile_time_secs: Optional[float] = None) -> str:
    """Point JAX at the shared cache and return the directory in force.

    `min_compile_time_secs` lowers JAX's persist threshold (default
    1 s) for callers that recompile many sub-second programs — the test
    suite."""
    import jax
    if min_compile_time_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_compile_time_secs)
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
