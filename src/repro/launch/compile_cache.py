"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, :mod:`repro.launch.train`,
:mod:`repro.launch.serve`, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up, so processes that run the
same programs share compiled executables across runs.  The cache key
includes the directory, so the directory is fixed: either the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself and
this module sets nothing), or ``.jax_cache`` at the root of the
checkout.
"""

from __future__ import annotations

import os

import jax

#: The checkout-local fallback (listed in .gitignore).
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
