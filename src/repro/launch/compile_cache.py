"""Where JAX keeps compiled programs between processes.

Compiling the served model's steps for the chip takes minutes; JAX's
persistent compilation cache lets a later process load them instead.
`enable_compile_cache()` is called by entry points (chip_smoke.py,
`python -m repro.launch.serve`), never at import time."""
from __future__ import annotations

import os

#: the fixed fallback directory: `.jax_cache/` at the repository root.
#: A fixed path, because the directory is part of every entry's key —
#: a temporary or per-process path would never hit.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    `$JAX_COMPILATION_CACHE_DIR` when set (JAX reads it itself, so no
    other directory is set here), else DEFAULT_DIR."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
