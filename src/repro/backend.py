"""What the program derives from the JAX backend it runs on.

  * ``interpret_kernels`` — Pallas kernels are emulated only where the
    default backend is the CPU; on an accelerator they always lower to
    Mosaic.  Code above ``repro.kernels`` has no switch for it, so a served
    path can never emulate its kernels on a chip.
  * ``enable_compile_cache`` — JAX's persistent compilation cache for entry
    points (the serving CLI, the benchmarks, ``chip_smoke.py``).  Never
    called at import, so tests do not fill it.
  * ``full_f32`` — the coded programs state f32 arithmetic; on a TPU an f32
    matmul or conv left at default precision rounds its operands to bf16,
    which the CRME decode inverse then amplifies.
"""
from __future__ import annotations

import functools
import os

import jax

__all__ = ["interpret_kernels", "enable_compile_cache", "full_f32",
           "COMPILE_CACHE_DIR"]

# <repo>/.jax_cache: a fixed path (the path is part of each entry's key, so
# a directory that moves never hits), listed in .gitignore
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def interpret_kernels() -> bool:
    """True only where the default JAX backend is the CPU."""
    return jax.default_backend() == "cpu"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to the checkout's
    fixed ``COMPILE_CACHE_DIR``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def full_f32(fn):
    """``fn`` with every matmul and conv it traces at ``HIGHEST`` precision
    (full f32 on a TPU; no change on the CPU).  The precision is fixed when
    ``fn`` is traced, so wrapping the function a ``jax.jit`` compiles is
    enough."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return traced
