"""Where compiled programs are kept between runs.

Every entry point (`chip_smoke.py`, `repro.launch.serve`,
`benchmarks.run`) calls `enable_compile_cache()` before its first compile.
JAX's persistent compilation cache is keyed partly by its directory, so
the directory is fixed: the one `JAX_COMPILATION_CACHE_DIR` names when it
is set (JAX reads that variable itself, and nothing else is set here), and
otherwise `.jax_cache/` at the root of this checkout (git-ignored).
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
