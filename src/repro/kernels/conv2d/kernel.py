"""Convolution as implicit GEMM for the TPU MXU.

Hardware adaptation (DESIGN.md §3): the paper's workers run a black-box CPU
convolution; on TPU the native form is im2col followed by an MXU-tiled GEMM.
The GEMM dims are ``M = H'*W'`` (output pixels), ``K = KH*KW*C`` (patch,
tap-major), ``N = out channels``.

Two im2col strategies:

  * **In-kernel im2col** (``fused_im2col=True``, the default) — patch
    extraction is fused into the GEMM tile load.  The wrapper lays each
    input share out as channel-last stride-phase planes
    (``_space_to_depth``), so every tap of every output pixel is a
    unit-stride window and channels ride the lane axis.  The grid walks
    (image share, output-row tile, N tile); at the first N tile of a row
    tile the kernel copies one ``(bo*W', C)`` window per tap into a VMEM
    patch scratch (plain 2-D window stores, which Mosaic lowers — no
    dynamic or strided value slices, no 4-D->2-D shape casts), and every N
    tile sweeps that scratch against its filters.  The
    ``(ea*B, C*KH*KW, H', W')`` patch tensor — the largest intermediate on
    the worker hot path — never exists in HBM.  When the whole share is
    too big for VMEM (uncoded full-frame convs), the **streamed** variant
    (``stream_k``) keeps the planes in HBM and copies in only each row
    tile's plane rows; the patch and the fp32 chunk order are the same, so
    it is bit-identical to the resident variant.
  * **Two-step** (``fused_im2col=False``, the fallback for odd geometries)
    — XLA's ``conv_general_dilated_patches`` materializes the patch tensor
    in HBM, reordered to the same tap-major columns, then one
    ``matmul_pallas`` tile sweep consumes it.

All paths accumulate fp32 over the same 128-sized K chunks in the same
order, so their outputs are bit-identical.  The output width is padded to
the 8-row sublane tile inside the fused kernel and sliced off after.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.backend import interpret_kernels
from repro.kernels.matmul.kernel import matmul_pallas

__all__ = ["conv2d_im2col_pallas", "coded_worker_pallas",
           "coded_transition_pallas"]

# Guard for the in-kernel im2col path: the input block (tile-padded phase
# planes) and one patch tile (bo*wo x K) must both fit VMEM comfortably.
# Geometries past the guard take the streamed variant, else the two-step
# path (the documented fallback).
_FUSED_VMEM_ELEMS = 1 << 21  # 2M fp32 elements = 8 MB of the ~16 MB VMEM


def conv2d_im2col_pallas(
    x: jnp.ndarray,
    k: jnp.ndarray,
    stride: int = 1,
    padding: int = 0,
    *,
    interpret: bool | None = None,
    **tile_kw,
) -> jnp.ndarray:
    """``x``: (C, H, W); ``k``: (N, C, KH, KW) -> (N, H', W').

    The degenerate one-share/one-group/one-image case of the fused worker
    kernel — delegating keeps a single owner for the im2col patch-ordering
    and GEMM-layout contract."""
    c, h, w = x.shape
    n, c2, kh, kw = k.shape
    assert c == c2
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding)))
    return coded_worker_pallas(x[None], k[None], stride, interpret=interpret,
                               **tile_kw)[0]


def _space_to_depth(xin: jnp.ndarray, stride: int, hs: int,
                    ws: int) -> jnp.ndarray:
    """``(G, C, hh, wp)`` -> channel-last phase planes ``(G, hs, ws, s*s*C)``
    with ``[g, a, b, (ph*s + pw)*C + c] = x[g, c, a*s + ph, b*s + pw]``
    (zero past the share's edge).

    Tap ``(dh, dw) = (qh*s + ph, qw*s + pw)`` of output pixel ``(oh, ow)``
    then reads plane ``(ph, pw)`` at ``(oh + qh, ow + qw)``: every tap is a
    unit-stride window, so the kernel needs no strided or dynamic value
    slices, and channels ride the lane axis."""
    g, c, hh, wp = xin.shape
    s = stride
    x = jnp.pad(xin, ((0, 0), (0, 0), (0, hs * s - hh), (0, ws * s - wp)))
    x = x.reshape(g, c, hs, s, ws, s).transpose(0, 2, 4, 3, 5, 1)
    x = x.reshape(g, hs, ws, s * s * c)
    # trailing dims padded to the (8, 128) VMEM tile: row-window copies of
    # the planes must slice whole tiles
    return jnp.pad(x, ((0, 0), (0, 0), (0, _pad_to(ws, 8) - ws),
                       (0, _ceil128(s * s * c) - s * s * c)))


def _fused_geometry(hh: int, wp: int, kh: int, kw: int, stride: int,
                    ho: int, wo: int) -> tuple[int, int, int]:
    """``(wo_p, hs, ws)``: the output width padded to the 8-row sublane tile
    (so ``(bo, wo_p, C) -> (bo*wo_p, C)`` is a layout-preserving reshape)
    and the phase-plane extent that covers every tap of the padded rows."""
    wo_p = _pad_to(wo, 8)
    hs = max(-(-hh // stride), (kh - 1) // stride + ho)
    ws = max(-(-wp // stride), (kw - 1) // stride + wo_p)
    return wo_p, hs, ws


def _gather_patch(win, lead: tuple, p_ref, r0, *, stride: int, kh: int,
                  kw: int, c: int, bo: int, wo_p: int):
    """Gather the ``(bo*wo_p, kp)`` patch tile of ``bo`` output rows into the
    VMEM scratch ``p_ref``.

    ``win[lead]``: phase planes ``(rows, ws, lanes)``; output row ``r`` of
    the tile reads plane rows ``r0 + r + qh``.  Patch columns are ordered
    ``(KH, KW, C)`` — one lane window of ``C`` columns per tap, so every
    store is a plain 2-D window write."""
    kp = p_ref.shape[1]
    ck = kh * kw * c
    if kp > ck:  # zero-pad K to the chunk grid (exact under fp32 addition)
        p_ref[:, ck:] = jnp.zeros((bo * wo_p, kp - ck), p_ref.dtype)
    for dh in range(kh):
        qh, ph = divmod(dh, stride)
        for dw in range(kw):
            qw, pw = divmod(dw, stride)
            t = dh * kw + dw
            lane = (ph * stride + pw) * c
            slab = win[lead + (pl.ds(r0 + qh, bo), pl.ds(qw, wo_p),
                               slice(None))]
            p_ref[:, t * c:(t + 1) * c] = slab[:, :, lane:lane + c].reshape(
                bo * wo_p, c)


def _patch_gemm(p_ref, w_ref, o_ref, bk: int):
    """Sweep the patch tile against one N tile of filters in ``bk`` chunks."""
    kp, bn = w_ref.shape
    acc = jnp.zeros((p_ref.shape[0], bn), jnp.float32)
    for kk in range(kp // bk):  # same chunk order as matmul_pallas: bit-compat
        acc += jnp.dot(
            p_ref[:, kk * bk:(kk + 1) * bk],
            w_ref[kk * bk:(kk + 1) * bk, :],
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    o_ref[...] = acc.astype(o_ref.dtype).reshape(o_ref.shape)


def _worker_im2col_kernel(x_ref, w_ref, o_ref, p_ref, *, bk: int, **geo):
    """One (share, output-row tile, N tile) step of the fused worker GEMM.

    ``x_ref``: ``(1, hs, ws, s*s*C)`` — the share's phase planes, streamed
    to VMEM whole by the pallas pipeline.  ``w_ref``: ``(kp, bn)`` — one
    N-tile of the reshaped coded filters.  The patch tile is gathered at
    the first N tile of each (share, row tile) and reused by the rest."""
    r0 = pl.program_id(1) * geo["bo"]

    @pl.when(pl.program_id(2) == 0)
    def _():
        _gather_patch(x_ref, (0,), p_ref, r0, **geo)

    _patch_gemm(p_ref, w_ref, o_ref, bk)


def _worker_im2col_stream_kernel(x_hbm, w_ref, o_ref, p_ref, buf, sem, *,
                                 span: int, bk: int, **geo):
    """Streamed variant of ``_worker_im2col_kernel``: the phase planes stay
    in HBM (``memory_space=HBM``) and each row tile copies in only the
    ``span`` plane rows its taps read — the whole-share VMEM block never
    exists.  The patch gather and the fp32 chunk order are the resident
    kernel's, so the two variants are bit-identical."""
    gi, r0 = pl.program_id(0), pl.program_id(1) * geo["bo"]

    @pl.when(pl.program_id(2) == 0)
    def _():
        copy = pltpu.make_async_copy(x_hbm.at[gi, pl.ds(r0, span)], buf,
                                     sem.at[0])
        copy.start()
        copy.wait()
        _gather_patch(buf, (), p_ref, 0, **geo)

    _patch_gemm(p_ref, w_ref, o_ref, bk)


def _fused_worker_gemm(xin, ke, stride, *, interpret, bo, bn, bk,
                       stream=False):
    """In-kernel-im2col GEMM: xin (G, C, hh, wp) x ke (eb, nb, C, KH, KW)
    -> (G, ho, wo, eb*nb).  ``stream=True`` keeps the share in HBM and
    copies each row tile's plane rows in (bit-identical output)."""
    g, c, hh, wp = xin.shape
    eb, nb, _, kh, kw = ke.shape
    ho = (hh - kh) // stride + 1
    wo = (wp - kw) // stride + 1
    assert ho % bo == 0, f"bo={bo} must divide H'={ho}"
    wo_p, hs, ws = _fused_geometry(hh, wp, kh, kw, stride, ho, wo)
    planes = _space_to_depth(xin, stride, hs, ws)
    _, _, ws, lanes = planes.shape
    ck = c * kh * kw
    n = eb * nb
    bk_ = min(bk, _ceil128(ck))
    kp = _pad_to(ck, bk_)
    bn_ = min(bn, _ceil128(n))
    np_ = _pad_to(n, bn_)
    w = _filters_khkwc(ke)  # (ck, N), K ordered (KH, KW, C) like the patch
    if (kp, np_) != (ck, n):
        w = jnp.pad(w, ((0, kp - ck), (0, np_ - n)))
    dtype = jnp.result_type(xin.dtype, ke.dtype)
    geo = dict(stride=stride, kh=kh, kw=kw, c=c, bo=bo, wo_p=wo_p, bk=bk_)
    scratch = [pltpu.VMEM((bo * wo_p, kp), xin.dtype)]
    if stream:
        span = bo + (kh - 1) // stride
        kernel = functools.partial(_worker_im2col_stream_kernel, span=span,
                                   **geo)
        x_spec = pl.BlockSpec(memory_space=pltpu.HBM)
        scratch += [pltpu.VMEM((span, ws, lanes), xin.dtype),
                    pltpu.SemaphoreType.DMA((1,))]
    else:
        kernel = functools.partial(_worker_im2col_kernel, **geo)
        x_spec = pl.BlockSpec((1, hs, ws, lanes),
                              lambda gi, i, j: (gi, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(g, ho // bo, np_ // bn_),
        in_specs=[x_spec, pl.BlockSpec((kp, bn_), lambda gi, i, j: (0, j))],
        out_specs=pl.BlockSpec((1, bo, wo_p, bn_),
                               lambda gi, i, j: (gi, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((g, ho, wo_p, np_), dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(planes, w)
    return out[:, :, :wo, :n]


def _filters_khkwc(ke: jnp.ndarray) -> jnp.ndarray:
    """Coded filter groups ``(eb, nb, C, KH, KW)`` as the ``(KH*KW*C, eb*nb)``
    GEMM operand, K ordered ``(KH, KW, C)`` — the patch column order of
    both im2col strategies."""
    eb, nb, c, kh, kw = ke.shape
    return ke.transpose(0, 1, 3, 4, 2).reshape(eb * nb, kh * kw * c).T


def _fused_vmem(xin_shape, kh: int, kw: int, stride: int, ho: int, wo: int,
                bo: int, *, stream: bool) -> int | None:
    """VMEM elements one grid step of the fused kernel holds: the input
    block (whole share, or a row tile's planes when ``stream``) and the
    patch tile.  None when ``bo`` does not tile ``H'``."""
    _, c, hh, wp = xin_shape
    if ho < 1 or wo < 1 or bo < 1 or ho % bo != 0:
        return None
    wo_p, hs, ws = _fused_geometry(hh, wp, kh, kw, stride, ho, wo)
    rows = bo + (kh - 1) // stride if stream else hs
    block = rows * _pad_to(ws, 8) * _ceil128(stride * stride * c)
    patch = bo * wo_p * _ceil128(c * kh * kw)
    return max(block, patch)


def _fused_feasible(xin_shape, kh: int, kw: int, stride: int, ho: int,
                    wo: int, bo: int) -> bool:
    """Geometry admits the whole-share-resident in-kernel im2col path."""
    elems = _fused_vmem(xin_shape, kh, kw, stride, ho, wo, bo, stream=False)
    return elems is not None and elems <= _FUSED_VMEM_ELEMS


def _stream_feasible(xin_shape, kh: int, kw: int, stride: int, ho: int,
                     wo: int, bo: int, bk: int) -> bool:
    """Geometry admits the streamed in-kernel im2col path: a row tile's
    plane rows, the patch tile and the whole w N-tile must fit VMEM — but
    the whole share need not."""
    elems = _fused_vmem(xin_shape, kh, kw, stride, ho, wo, bo, stream=True)
    ck = xin_shape[1] * kh * kw
    kp = _pad_to(ck, min(bk, _ceil128(ck)))
    return (elems is not None and elems <= _FUSED_VMEM_ELEMS
            and kp * 128 <= _FUSED_VMEM_ELEMS)


def default_bo(ho: int, wo: int, target: int = 256) -> int:
    """Largest divisor of ``ho`` whose M tile (bo*wo patch rows) stays near
    ``target`` rows — full-height tiles for the small shares coded layers
    produce, split tiles when H' is large."""
    best = 1
    for cand in range(1, ho + 1):
        if ho % cand == 0 and cand * wo <= target:
            best = cand
    return best


def coded_worker_pallas(
    xe: jnp.ndarray,
    ke: jnp.ndarray,
    stride: int = 1,
    *,
    interpret: bool | None = None,
    fused_im2col: bool | None = None,
    stream_k: bool | None = None,
    bo: int | None = None,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    num_buffers: int = 2,
) -> jnp.ndarray:
    """One worker's entire fused coded subtask as a single MXU tile sweep.

    The paper's Algorithm 4 runs ``ell_a * ell_b`` pairwise convolutions per
    worker; here they collapse into ONE implicit-GEMM sweep: the ``ell_a``
    coded input shares (x the request batch B) ride the GEMM M dimension and
    the ``ell_b`` coded filter groups concatenate into the N dimension — one
    kernel launch per worker per layer instead of ``ell_a * ell_b * B`` tiny
    unbatched GEMMs.

    ``xe``: coded input shares ``(ell_a, [B,] C, h_hat, Wp)`` — already
    conv-padded by APCP, so the patch extraction is VALID.
    ``ke``: coded filter groups ``(ell_b, N/k_b, C, KH, KW)``.
    Returns ``(ell_a*ell_b, [B,] N/k_b, H'/k_a, W')``, slot
    ``ell_b * b1 + b2`` (same layout as the unfused loop).

    ``fused_im2col`` selects the im2col strategy (module docstring); None =
    in-kernel when the geometry admits it.  ``stream_k`` picks the fused
    path's share residency: True forces the streamed variant (planes in
    HBM, each row tile's plane rows copied to VMEM), False forces
    whole-share-resident, None auto-falls-back to streaming when the share
    is too big for the resident path — so uncoded full-frame convs still
    take the fused path.  Both variants are bit-identical.
    ``interpret=None`` emulates the kernels only on a CPU backend.
    ``bo`` is the fused path's output-row tile (must divide H'; None =
    ``default_bo``);
    ``bm/bn/bk/num_buffers`` tile the GEMM (``bm``/``num_buffers`` drive
    the two-step path's ``matmul_pallas``; the fused path streams shares
    at grid level).
    """
    interpret = interpret_kernels() if interpret is None else interpret
    batched = xe.ndim == 5
    ea = xe.shape[0]
    b = xe.shape[1] if batched else 1
    c, hh, wp = xe.shape[-3:]
    eb, nb, c2, kh, kw = ke.shape
    assert c == c2, (xe.shape, ke.shape)
    xin = xe.reshape(ea * b, c, hh, wp)
    ho = (hh - kh) // stride + 1
    wo = (wp - kw) // stride + 1
    bo_ = bo if bo is not None else default_bo(ho, wo)
    stream = bool(stream_k)
    if fused_im2col is None:
        if stream_k is True:
            fused_im2col = True
        elif _fused_feasible(xin.shape, kh, kw, stride, ho, wo, bo_):
            fused_im2col = True
        elif stream_k is None and _stream_feasible(xin.shape, kh, kw, stride,
                                                   ho, wo, bo_, bk):
            fused_im2col = stream = True
        else:
            fused_im2col = False
    if fused_im2col:
        out = _fused_worker_gemm(xin, ke, stride, interpret=interpret,
                                 bo=bo_, bn=bn, bk=bk,
                                 stream=stream)  # (G, ho, wo, eb*nb)
        y = out.reshape(ea, b, ho, wo, eb, nb)
    else:
        patches = jax.lax.conv_general_dilated_patches(
            xin,
            filter_shape=(kh, kw),
            window_strides=(stride, stride),
            padding=((0, 0), (0, 0)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )  # (ea*B, C*KH*KW, H', W') — materialized in HBM, then GEMM'd
        ck = c * kh * kw
        # M = ea*B*H'*W' output pixels, K = KH*KW*C patch (the fused path's
        # column order), N = eb*(N/k_b)
        lhs = patches.reshape(ea * b, c, kh * kw, ho, wo).transpose(
            0, 3, 4, 2, 1).reshape(ea * b * ho * wo, ck)
        rhs = _filters_khkwc(ke)
        out = matmul_pallas(lhs, rhs, interpret=interpret, bm=bm, bn=bn,
                            bk=bk, num_buffers=num_buffers)  # (M, eb*nb)
        y = out.reshape(ea, b, ho, wo, eb, nb)
    y = jnp.transpose(y, (0, 4, 1, 5, 2, 3)).reshape(ea * eb, b, nb, ho, wo)
    return y if batched else y[:, 0]


def coded_transition_pallas(
    outs: jnp.ndarray,
    d: jnp.ndarray,
    m_next: jnp.ndarray,
    assemble,
    *,
    interpret: bool | None = None,
    decode_kw: dict | None = None,
    encode_kw: dict | None = None,
) -> jnp.ndarray:
    """One partition-resident layer transition: decode-GEMM (ReLU fused into
    the tile-sweep epilogue) -> partition-space pool/halo re-slice ->
    encode-GEMM, compiled as a single program.

    The round-trip path runs decode+merge, a separate elementwise
    relu/pool over the assembled ``([B,] N, H', W')`` tensor, then
    ``apcp_partition`` + encode from scratch.  Here the activation never
    leaves partition space: the decode is one MXU tile sweep over
    ``d (Q, Q) @ rows (Q, F)`` with the ReLU applied in-register at the
    flush (``matmul_pallas(relu=True)``), ``assemble`` (the
    geometry-specialized ``partition_transition`` closure passed in from
    ``CodedPipeline`` — pure static slicing/max, traced inline) exchanges
    halo rows and re-slices the pooled partitions, and the re-encode is a
    second tile sweep ``m_next^T (L, k_a') @ parts (k_a', F')``.  The pool
    between the two GEMMs is a nonlinearity, so two sweeps is the minimum —
    but both run inside one jitted program with no merged-tensor round trip.

    ``outs``: fastest-delta worker outputs ``(delta, ell2, *block)``;
    ``d``: the ``(Q, Q)`` decode inverse; ``m_next``: the next layer's
    A-code encode columns ``(k_a', L)``.  Returns the coded next-layer
    input shares ``(L, *part)`` (worker-grouping is the caller's job).
    ``decode_kw``/``encode_kw`` pass explicit tile/buffer overrides to the
    two ``matmul_pallas`` sweeps; when omitted, the autotune ledger is
    consulted per GEMM cell at trace time (lookup only — never a sweep).
    """
    from repro.kernels import autotune

    interpret = interpret_kernels() if interpret is None else interpret
    q = d.shape[0]
    rows = outs.reshape(outs.shape[0] * outs.shape[1], -1)
    if decode_kw is None:
        decode_kw = autotune.matmul_params(
            q, q, rows.shape[1], relu=True, interpret=interpret) or {}
    decoded = matmul_pallas(
        d.astype(rows.dtype), rows, relu=True, interpret=interpret,
        **decode_kw
    )
    blocks = decoded.reshape((q,) + outs.shape[2:])
    parts = assemble(blocks)  # (k_a', [B,] C, h_hat', W'+2p')
    k2 = parts.shape[0]
    cols = m_next.astype(parts.dtype)  # (k_a', L)
    flat = parts.reshape(k2, -1)
    if encode_kw is None:
        encode_kw = autotune.matmul_params(
            cols.shape[1], k2, flat.shape[1], interpret=interpret) or {}
    coded = matmul_pallas(cols.T, flat, interpret=interpret, **encode_kw)
    return coded.reshape((cols.shape[1],) + parts.shape[1:])


def _ceil128(x: int) -> int:
    return -(-x // 128) * 128


def _pad_to(x: int, b: int) -> int:
    return -(-x // b) * b
