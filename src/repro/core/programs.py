"""Jit names of the coded path's device programs.

The jit sites (the encoder, decoder and transition programs of
``CodedPipeline`` and both worker pools' worker program) take their
function names from ``PROGRAMS`` through ``named``, so a trace reader finds
them as ``jit_<name>`` whatever the Python functions are called.
"""
from __future__ import annotations

import functools

PROGRAMS = {
    "worker": "worker_compute",
    "encode": "encode_inputs",
    "decode": "dec",
    "transition": "trans",
}


def named(fn, program: str):
    """``fn`` under the function name ``PROGRAMS[program]``, for
    ``jax.jit`` to compile as ``jit_<name>``."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        return fn(*args, **kwargs)

    run.__name__ = run.__qualname__ = PROGRAMS[program]
    return run
