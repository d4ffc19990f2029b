"""Persistent XLA compilation cache, placed from outside or at one path.

A process on the chip starts with no compiled code, and the fused train
steps and the LM step take tens of seconds each to compile. The cache's
directory is part of its key, so it must not move between runs:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, jax binds it itself and
  this module sets no directory — whoever runs the program owns the
  placement;
- otherwise the cache lives at :data:`DEFAULT_DIR`, ``.jax_cache`` at
  the root of this checkout (listed in ``.gitignore``), the same from
  every working directory.

Every entry point calls :func:`enable` once before its first jit
(``Postoffice.start``, ``apps/lm/main.py``, ``chip_smoke.py``).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable() -> str:
    """Turn the persistent compilation cache on and return its
    directory. Idempotent; initializes no backend, so it is safe before
    the ``jax.distributed`` rendezvous."""
    import jax

    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the sub-second helper jits add up to a large share of a cold
    # start on the chip; cache them too, not only the big programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
