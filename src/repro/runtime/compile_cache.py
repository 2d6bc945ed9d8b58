"""JAX's persistent compilation cache, at one fixed place per checkout.

A cold run of the detection service compiles every bucket's programs (with
``max_edges="auto"`` each holds a ``lax.switch`` over its Pallas vote
tiers) plus the fused kernel's; the cache lets a second process on the
same machine skip all of it.  The cache key includes the directory, so the
directory must not move between runs: it is either the one the
environment names (``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself)
or ``.jax_cache/`` at the root of the checkout (git-ignored) — never a
temporary, per-process or per-run path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    Call before the first compile.  Entries are written however short
    their compile was, so the small kernels are cached too.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
