"""Public conv ops used by CodedConv2d's ``backend='pallas'`` path.

The kernels emulate themselves only where the default backend is the CPU
(``repro.backend.interpret_kernels``); on a TPU they lower to Mosaic.

When the caller passes no explicit tile/strategy kwargs, the autotune
ledger (``repro.kernels.autotune``) is consulted at trace time — shapes are
concrete under tracing, the lookup never sweeps, and tile sizes are static
kernel args, so a tuned program costs the same single jit trace per
(geometry, bucket) an untuned one does.
"""
from repro.kernels import autotune

from .kernel import (
    coded_transition_pallas,
    coded_worker_pallas,
    conv2d_im2col_pallas,
)

__all__ = ["conv2d_im2col", "coded_worker", "coded_transition"]


def conv2d_im2col(x, k, stride=1, padding=0, **tile_kw):
    return conv2d_im2col_pallas(x, k, stride, padding, **tile_kw)


def coded_worker(xe, ke, stride=1, **tile_kw):
    """Fused batched coded-worker subtask: one implicit-GEMM tile sweep.

    No explicit ``tile_kw`` -> the autotuned winner for this
    (shares, filters, stride) cell, when one is in the ledger.
    """
    if not tile_kw:
        tile_kw = autotune.worker_params(
            tuple(xe.shape), tuple(ke.shape), stride) or {}
    return coded_worker_pallas(xe, ke, stride, **tile_kw)


def coded_transition(outs, d, m_next, assemble, **kw):
    """Fused partition-resident layer transition: decode-GEMM with ReLU
    epilogue -> partition-space pool/halo re-slice -> encode-GEMM.  The two
    GEMM sweeps consult the autotune ledger unless ``decode_kw``/
    ``encode_kw`` are passed."""
    return coded_transition_pallas(outs, d, m_next, assemble, **kw)
