"""Where the entry points keep JAX's persistent compilation cache.

A cold start of the serving step at published widths spends most of its
time compiling, and a second process with the same programs can read
them back.  ``use_compile_cache()`` is called once at start-up by the
command-line entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``); tests never call it.

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX already
  reads it, and nothing here overrides it.
* otherwise: ``<checkout>/.jax_cache`` (gitignored).  The path is fixed —
  never a temp name, pid or time — because the directory is part of
  where a later run looks.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
