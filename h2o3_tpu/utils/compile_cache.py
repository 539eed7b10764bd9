"""Where XLA's persistent compilation cache lives — the ONE place.

A cold trainer or server compiles every device program it dispatches (the
K-tree GBM program alone costs about a minute on a v5e), so every process
that forms a cloud on an accelerator (`parallel.mesh.init`:
`h2o3_tpu.init`, `python -m h2o3_tpu`, the REST server, chip_smoke.py;
bench.py calls `enable` itself) keeps a persistent cache. Placement comes
from OUTSIDE when the deployment says so: with `JAX_COMPILATION_CACHE_DIR`
set, JAX reads it itself and nothing is set in code. Otherwise the cache
sits at a FIXED path beside the package — the directory is part of the
cache key, so a temp name, a pid or a time in it would never hit.
"""

from __future__ import annotations

import os

import jax


def cache_dir() -> str:
    """The directory in force: `JAX_COMPILATION_CACHE_DIR`, else
    <checkout>/.jax_cache computed from the package's own location."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable() -> str | None:
    """Point JAX at `cache_dir()` (setting nothing when the variable
    already did); returns the directory. A no-op on the CPU backend
    unless the variable asks for it: host programs compile in
    milliseconds to seconds, and XLA:CPU reloads a cached result with a
    wall of machine-feature complaints per hit."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return cache_dir()
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", cache_dir())
    return cache_dir()
