"""Where JAX keeps its persistent compilation cache.

The cache is keyed by the directory it lives in, so the directory must
not move between runs: ``$JAX_COMPILATION_CACHE_DIR`` when it is set,
otherwise a fixed ``.jax_cache/`` at the root of this checkout.  Entry
points call ``enable_compile_cache()`` before their first compile;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory for this environment (``os.environ`` if None)."""
    env = os.environ if environ is None else environ
    return env.get(ENV_VAR) or str(CHECKOUT_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
