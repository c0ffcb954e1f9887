"""Settings the program derives from the platform instead of taking options.

Two rules live here, and nowhere else:

  * Pallas kernels COMPILE on a TPU and run in interpret mode everywhere
    else (``pallas_interpret``). No config field selects the mode, so a
    served step on the chip can never silently fall back to the Pallas
    interpreter. Kernel-level calls may still pass an explicit ``interpret``
    (tests that pin interpret mode, compile rehearsals for a described chip).
  * JAX's persistent compilation cache (``enable_compile_cache``): the
    directory named by ``JAX_COMPILATION_CACHE_DIR`` when the environment
    sets one, otherwise a fixed ``.jax_cache/`` inside the checkout — a
    path that never moves between runs, so a second run finds the first
    run's executables.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

# the checkout root: src/repro/platform.py -> ../..
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas execution mode: an explicit bool wins, else compiled on a TPU
    and interpreted elsewhere."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the first compile. When ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX already reads it and nothing is set here.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
