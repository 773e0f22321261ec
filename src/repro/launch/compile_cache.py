"""Where JAX keeps the persistent compilation cache for this repo's entry
points.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``examples/``, ``benchmarks/run.py``) calls
:func:`configure` once, before it compiles anything.  A set
``JAX_COMPILATION_CACHE_DIR`` wins and nothing here overrides it (JAX reads
the variable itself).  Otherwise the cache goes to :data:`DEFAULT_DIR`, one
fixed, git-ignored directory inside the checkout: a path built from a
temporary name, a pid or the time would never be hit again.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<checkout>/.jax_cache`` — listed in ``.gitignore``.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
