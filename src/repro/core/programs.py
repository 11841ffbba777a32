"""Jit names of the coded path's device programs, and the served worker
program.

The jit sites (the encoder, decoder and transition programs of
``CodedPipeline`` and both worker pools' worker program) take their
function names from ``PROGRAMS`` through ``named``, so a trace reader finds
them as ``jit_<name>`` whatever the Python functions are called.
"""
from __future__ import annotations

import functools

import jax

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


def worker_share_program(compute):
    """The jitted worker program one device serves to all n workers, as
    ``jit_worker_compute``: ``(xe, ke, i)`` takes every worker's stacked
    coded inputs and resident coded filters and the worker's index as an
    int32 device scalar, and runs ``compute(xe[i], ke[i])``.  The share is
    selected inside the compiled program, so one program per shape serves
    every worker and a worker thread makes no eager indexing dispatch.
    Called as ``(xe_i, ke_i)`` it runs on a share selected already."""
    def select(xe, ke, i=None):
        if i is not None:
            xe, ke = xe[i], ke[i]
        return compute(xe, ke)

    return jax.jit(named(select, "worker"))
