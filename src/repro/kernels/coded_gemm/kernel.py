"""CRME encode/decode as a skinny GEMM on the shared matmul lowering.

Both NSCTC phases are ``small code matrix (Q x Q or k x 2n) @ wide feature
matrix (rows x F)`` products.  ``coded_gemm_pallas`` rides the
multi-buffered ``matmul_pallas`` lowering (async-DMA operand streaming,
autotunable tiles) instead of carrying its own single-purpose kernel: the
code matrix always fits one K tile, so the accumulation order — one MXU
dot per feature tile — is identical to the legacy lowering and the outputs
are bit-equal (tests/test_kernels.py proves it).

``coded_gemm_pallas_legacy`` keeps the original feature-axis-only kernel
as the parity reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import interpret_kernels
from repro.kernels.matmul.kernel import matmul_pallas

__all__ = ["coded_gemm_pallas", "coded_gemm_pallas_legacy"]


def coded_gemm_pallas(
    code: jnp.ndarray,
    feats: jnp.ndarray,
    *,
    interpret: bool | None = None,
    bm: int = 128,
    bn: int = 512,
    bk: int = 128,
    num_buffers: int = 2,
) -> jnp.ndarray:
    """``code`` (R_out, R_in) @ ``feats`` (R_in, F) -> (R_out, F).

    R_* are code dimensions (tiny — the whole code matrix fits one
    (bm, bk) tile after padding); F is the flattened tensor-block feature
    axis.  Tile kwargs default to the legacy shape (one row-block, 512-wide
    feature tiles) and are overridable from the autotune ledger.
    """
    return matmul_pallas(
        code, feats, bm=bm, bn=bn, bk=bk,
        interpret=interpret, num_buffers=num_buffers,
    )


def _coded_kernel(m_ref, t_ref, o_ref):
    o_ref[...] = jnp.dot(
        m_ref[...], t_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bf", "interpret"))
def coded_gemm_pallas_legacy(
    code: jnp.ndarray,
    feats: jnp.ndarray,
    *,
    bf: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """The pre-rebase lowering (feature-axis grid only): kept as the
    bit-parity reference for the matmul-backed path."""
    interpret = interpret_kernels() if interpret is None else interpret
    r_out, r_in = code.shape
    r_in2, f = feats.shape
    assert r_in == r_in2

    r_out_p = -(-r_out // 8) * 8
    r_in_p = -(-r_in // 8) * 8
    bf_ = min(bf, -(-f // 128) * 128)
    fp = -(-f // bf_) * bf_
    code = jnp.pad(code, ((0, r_out_p - r_out), (0, r_in_p - r_in)))
    feats = jnp.pad(feats, ((0, r_in_p - r_in), (0, fp - f)))

    out = pl.pallas_call(
        _coded_kernel,
        grid=(fp // bf_,),
        in_specs=[
            pl.BlockSpec((r_out_p, r_in_p), lambda i: (0, 0)),
            pl.BlockSpec((r_in_p, bf_), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((r_out_p, bf_), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r_out_p, fp), feats.dtype),
        interpret=interpret,
    )(code, feats)
    return out[:r_out, :f]
