"""Public encode/decode ops built on the coded-GEMM kernel.

Every op consults the persistent autotune ledger (``kernels/autotune``)
for its (m, k, n) cell when the caller passes no explicit tile kwargs —
the same lookup-never-sweeps discipline as the ``matmul`` op, so the
bounded-trace contract holds (a ledger miss just takes the defaults).
"""
import jax.numpy as jnp

from repro.kernels import autotune

from .kernel import coded_gemm_pallas

__all__ = ["crme_encode", "crme_decode", "coded_gemm"]


def _tuned(m: int, k: int, n: int) -> dict:
    params = autotune.matmul_params(m, k, n)
    if not params:
        return {}
    return {k_: v for k_, v in params.items()
            if k_ in ("bm", "bn", "bk", "num_buffers")}


def coded_gemm(code, feats, **kw):
    if not kw:
        kw = _tuned(code.shape[0], code.shape[1], feats.shape[1])
    return coded_gemm_pallas(code, feats, **kw)


def crme_encode(parts, matrix):
    """``parts`` (k, *block), ``matrix`` (k, ell*n) -> (ell*n, *block)."""
    k = parts.shape[0]
    rows = parts.reshape(k, -1)
    m = jnp.asarray(matrix, dtype=parts.dtype)
    out = coded_gemm(m.T, rows)
    return out.reshape((m.shape[1],) + parts.shape[1:])


def crme_decode(decode_matrix, coded):
    """``decode_matrix`` (Q, Q) = inv(E^T); ``coded`` (Q, *block)."""
    q = coded.shape[0]
    rows = coded.reshape(q, -1)
    d = jnp.asarray(decode_matrix, dtype=coded.dtype)
    out = coded_gemm(d, rows)
    return out.reshape(coded.shape)
