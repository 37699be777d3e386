"""Choices the program derives from the JAX backend it runs on.

* :func:`pallas_interpret` — every Pallas kernel (the hand-written ones in
  :mod:`repro.kernels` and the DSL's ``pallas`` target) runs in interpret
  mode only when the default backend is the CPU.  On an accelerator the
  kernels always compile through Mosaic; nothing falls back.
* :func:`enable_compile_cache` — JAX's persistent compilation cache, set
  up once by each entry point (never on import of :mod:`repro`).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the compile cache of a checkout when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed path (the path is part of what the cache can hit on)
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def pallas_interpret() -> bool:
    """True only when the default JAX backend is the CPU."""
    return jax.default_backend() == "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself, and no other directory is set here); otherwise the cache goes
    to :data:`DEFAULT_COMPILE_CACHE` inside the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["pallas_interpret", "enable_compile_cache",
           "DEFAULT_COMPILE_CACHE"]
