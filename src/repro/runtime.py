"""How this process runs JAX: kernel mode and the persistent compile cache.

* :func:`kernel_interpret` is the one place the Pallas kernels decide
  between compiled and interpreted execution.  Every kernel entry point
  takes ``interpret=None`` and resolves it here, so nothing on the served
  path can interpret a kernel on a TPU by default.
* :func:`use_compile_cache` is what every entry point (``chip_smoke.py``,
  ``benchmarks/bench_*.py``, ``scripts/*_smoke.py``) calls first, so that
  processes share compiled programs across runs.
"""

from __future__ import annotations

import os
import pathlib

import jax

# A fixed path inside the checkout: the cache key includes the directory,
# so a path that moves between runs (a temp dir, a pid) would never hit.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def kernel_interpret() -> bool:
    """True unless the arrays live on a TPU.

    Arrays are created with ``jnp.asarray`` / ``jax.device_put`` onto
    ``jax.devices()``, so their platform is the default backend's."""
    return jax.default_backend() != "tpu"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here.  Otherwise the cache goes to ``.jax_cache/``
    at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
