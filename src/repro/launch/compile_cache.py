"""Where JAX's persistent compilation cache lives.

The cache key includes its directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and nothing here overrides it), else a
fixed ``.jax_cache/`` at the root of the checkout (listed in
``.gitignore``). Entry points call ``configure_compile_cache()`` once at
start-up, before their first compile: ``chip_smoke.py``,
``python -m repro.launch.serve`` and ``benchmarks/run.py``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
