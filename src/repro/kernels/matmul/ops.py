"""Jit'd public wrapper for the tiled matmul kernel."""
from repro.kernels import autotune

from .kernel import matmul_pallas

__all__ = ["matmul"]


def matmul(a, b, **kw):
    if not kw:  # no explicit tiles: consult the autotune ledger (trace-time)
        kw = autotune.matmul_params(a.shape[0], a.shape[1], b.shape[1]) or {}
    return matmul_pallas(a, b, **kw)
