"""JAX's persistent compilation cache, kept where the next run finds it.

`enable()` is called from the entry points' `main()` (chip_smoke.py,
benchmarks/run.py, the examples), never while a module is imported. With
JAX_COMPILATION_CACHE_DIR set, JAX takes the directory from the environment
and this module sets no other. Without it the cache lives at the fixed
`<repo root>/.jax_cache`, which git ignores. The directory is part of what
a cache hit needs, so it never comes from a temporary name, a pid or the
clock.
"""
from __future__ import annotations

import os
from pathlib import Path

from repro.engine.tune import repo_root

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable() -> Path:
    """Turn the persistent cache on and return its directory."""
    import jax
    if not os.environ.get(ENV):
        root = repo_root() or Path.cwd()
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    # cache every program, the many sub-second kernel compiles included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return Path(jax.config.jax_compilation_cache_dir)
