"""MXU-tiled matmul Pallas kernel with multi-buffered operand streaming.

TPU mapping: blocks are multiples of (8, 128) fp32 register tiles; the MXU
consumes 128x128 operands, so default blocks are 128-aligned.  Accumulation
is fp32 in a VMEM scratch across the K steps (innermost), written back once
after the last K step — one HBM write per output tile.

Two lowering strategies share the same math (identical bk-chunked fp32
accumulation order, so their outputs are bit-identical):

  * ``num_buffers == 1`` — the classic 3-D grid sweep: pallas streams one
    (bm, bk) x (bk, bn) operand pair per grid step via ``BlockSpec``.  One
    VMEM buffer per operand; no explicit overlap.
  * ``num_buffers >= 2`` — pipelined operand streaming: the grid covers
    only (M, N) tiles, operands stay in HBM (``memory_space=ANY``), and the
    kernel walks K itself, rotating each operand through ``num_buffers``
    VMEM slots with explicit async DMA — the HBM->VMEM copy of K-step t+1
    (and beyond, up to ``num_buffers - 1`` steps ahead) overlaps the MXU
    compute of step t.  Double buffering is the default; quad buffering is
    the knob for deeper DMA latency hiding on real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.backend import interpret_kernels

__all__ = ["matmul_pallas"]


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
                   relu: bool = False):
    """Single-buffered body: K is the innermost grid dimension."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        if relu:  # fused epilogue: applied in-register before the HBM write
            acc = jnp.maximum(acc, 0.0)
        o_ref[...] = acc.astype(o_ref.dtype)


def _matmul_stream_kernel(a_hbm, b_hbm, o_ref, a_buf, b_buf, a_sem, b_sem,
                          acc_ref, *, k_steps: int, bm: int, bn: int, bk: int,
                          num_buffers: int, relu: bool = False):
    """Pipelined body: grid covers (M, N); the kernel streams K itself.

    Each operand rotates through ``num_buffers`` VMEM slots.  The copy for
    K-step ``t + num_buffers`` is issued right after step ``t``'s compute
    releases its slot, so up to ``num_buffers - 1`` DMAs are always in
    flight behind the MXU.
    """
    i, j = pl.program_id(0), pl.program_id(1)

    def a_dma(slot, kk):
        return pltpu.make_async_copy(
            a_hbm.at[pl.ds(i * bm, bm), pl.ds(kk * bk, bk)],
            a_buf.at[slot], a_sem.at[slot],
        )

    def b_dma(slot, kk):
        return pltpu.make_async_copy(
            b_hbm.at[pl.ds(kk * bk, bk), pl.ds(j * bn, bn)],
            b_buf.at[slot], b_sem.at[slot],
        )

    # fill the pipeline: one in-flight copy per buffer slot
    for s in range(min(num_buffers, k_steps)):
        a_dma(s, s).start()
        b_dma(s, s).start()

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(kk, _):
        slot = jax.lax.rem(kk, num_buffers)
        a_dma(slot, kk).wait()
        b_dma(slot, kk).wait()
        acc_ref[...] += jnp.dot(
            a_buf[slot], b_buf[slot], preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        # the compute above released this slot — refill it from k-step
        # kk + num_buffers while the other slots' copies keep the MXU fed
        @pl.when(kk + num_buffers < k_steps)
        def _prefetch():
            a_dma(slot, kk + num_buffers).start()
            b_dma(slot, kk + num_buffers).start()

        return 0

    jax.lax.fori_loop(0, k_steps, step, 0)
    acc = acc_ref[...]
    if relu:  # fused epilogue, identical to the single-buffered flush
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype",
                              "relu", "num_buffers")
)
def matmul_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
    relu: bool = False,
    num_buffers: int = 2,
) -> jnp.ndarray:
    """``a @ b`` with explicit VMEM tiling.  Shapes padded to block grid.

    ``interpret=None`` emulates the kernel where the default backend is the
    CPU and lowers it to Mosaic anywhere else (``repro.backend``).

    ``relu=True`` fuses ``max(., 0)`` into the flush epilogue — the output
    tile is rectified in-register on the last K step, so a GEMM-then-ReLU
    consumer (the coded transition's decode) costs no extra pass over HBM.

    ``num_buffers`` selects the lowering: 1 = the single-buffered 3-D grid
    sweep, >= 2 = pipelined operand streaming through that many VMEM slots
    per operand (module docstring).  Both accumulate fp32 over the same
    bk-sized K chunks in the same order, so outputs are bit-identical.

    Block-aligned operands skip the pad entirely (and the trailing slice),
    so the aligned fast path costs zero extra HBM copies.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if num_buffers < 1:
        raise ValueError(f"num_buffers must be >= 1, got {num_buffers}")
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    interpret = interpret_kernels() if interpret is None else interpret

    bm_, bn_, bk_ = (min(bm, _ceil8(m)), min(bn, _ceil128(n)), min(bk, _ceil128(k)))
    mp, np_, kp = _pad_to(m, bm_), _pad_to(n, bn_), _pad_to(k, bk_)
    if (mp, kp) != (m, k):  # aligned fast path: no pad, no extra HBM copy
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    k_steps = kp // bk_

    if num_buffers == 1:
        out = pl.pallas_call(
            functools.partial(_matmul_kernel, k_steps=k_steps, relu=relu),
            grid=(mp // bm_, np_ // bn_, k_steps),
            in_specs=[
                pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
            interpret=interpret,
        )(a, b)
    else:
        out = pl.pallas_call(
            functools.partial(
                _matmul_stream_kernel, k_steps=k_steps, bm=bm_, bn=bn_,
                bk=bk_, num_buffers=num_buffers, relu=relu,
            ),
            grid=(mp // bm_, np_ // bn_),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((bm_, bn_), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
            scratch_shapes=[
                pltpu.VMEM((num_buffers, bm_, bk_), a.dtype),
                pltpu.VMEM((num_buffers, bk_, bn_), b.dtype),
                pltpu.SemaphoreType.DMA((num_buffers,)),
                pltpu.SemaphoreType.DMA((num_buffers,)),
                pltpu.VMEM((bm_, bn_), jnp.float32),
            ],
            interpret=interpret,
        )(a, b)
    if (mp, np_) == (m, n):
        return out
    return out[:m, :n]


def _ceil8(x: int) -> int:
    return -(-x // 8) * 8


def _ceil128(x: int) -> int:
    return -(-x // 128) * 128


def _pad_to(x: int, b: int) -> int:
    return -(-x // b) * b
