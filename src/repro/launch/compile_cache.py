"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, :mod:`repro.launch.train`,
:mod:`repro.launch.serve`, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up, so processes that run the
same programs share compiled executables across runs.  The cache key
includes the directory, so the directory is fixed: either the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself and
this module sets nothing), or ``.jax_cache`` at the root of the
checkout.

The key also covers the programs' metadata (each operation's
``op_name``, which carries the named scopes of :mod:`repro.obs.profile`,
and its source location).  JAX leaves it out by default, and an
executable compiled before a scope was added would then be found again:
its operations would reach the profiler under their old names, and the
per-layer readings that key on the scopes would read nothing.  Source
files enter the metadata relative to the checkout's root, so that two
checkouts of the same code at different paths share their entries.
"""

from __future__ import annotations

import os
import re

import jax

#: The root of the checkout.
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
#: The checkout-local fallback (listed in .gitignore).
DEFAULT_DIR = os.path.join(ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(ROOT + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
