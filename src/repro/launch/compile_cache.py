"""Persistent XLA compilation cache for the program's entry points.

Entry points (`chip_smoke.py`, `repro.launch.serve`, `benchmarks.run`) call
`enable_compile_cache()` once at start-up, so a second process in the same
checkout loads its compiled programs instead of compiling them again. Library
code and tests never call it: importing a module changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed cache directory inside the checkout (listed in .gitignore); a path
#: that moved between runs would never hit
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent compilation cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and no
    other path is set here; otherwise the cache lives in `CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return CACHE_DIR
