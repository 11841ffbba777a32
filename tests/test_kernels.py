"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.coded_gemm import coded_gemm, coded_gemm_ref, crme_decode, crme_encode
from repro.kernels.conv2d import conv2d_im2col, conv2d_ref
from repro.kernels.matmul import matmul, matmul_ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("m,k,n", [
    (7, 5, 9), (128, 128, 128), (130, 257, 64), (1, 300, 1), (200, 64, 384),
    (8, 8, 8), (129, 1, 129),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_matmul_sweep(m, k, n, dtype):
    a = jnp.asarray(RNG.standard_normal((m, k)).astype(dtype))
    b = jnp.asarray(RNG.standard_normal((k, n)).astype(dtype))
    y = matmul(a, b)
    r = matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(r, np.float32), atol=2e-2, rtol=2e-2
    )


@pytest.mark.parametrize("shape", [
    (3, 12, 10, 8, 3, 3, 1, 1),
    (2, 16, 9, 5, 3, 2, 2, 0),
    (1, 7, 7, 4, 5, 5, 1, 2),
    (4, 9, 9, 3, 1, 1, 1, 0),
])
def test_conv2d_sweep(shape):
    C, H, W, N, KH, KW, s, p = shape
    x = jnp.asarray(RNG.standard_normal((C, H, W)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((N, C, KH, KW)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(conv2d_im2col(x, k, s, p)),
        np.asarray(conv2d_ref(x, k, s, p)),
        atol=1e-3,
    )


@pytest.mark.parametrize("m,k,n", [
    (7, 5, 9),        # odd everything: pad + trailing slice
    (128, 256, 128),  # block-aligned: the skip-pad fast path
    (16, 16, 3600),   # skinny decode-GEMM shape (q x q x F)
    (1, 300, 1),
])
@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_matmul_pipelined_bit_parity(m, k, n, relu, dtype):
    """The multi-buffered streaming lowering (num_buffers >= 2) is
    bit-identical to the single-buffered grid-K kernel: same bk-chunk fp32
    accumulation order, K zero-padding exact under fp32 addition."""
    from repro.kernels.matmul.kernel import matmul_pallas

    a = jnp.asarray(RNG.standard_normal((m, k)).astype(dtype))
    b = jnp.asarray(RNG.standard_normal((k, n)).astype(dtype))
    ref = np.asarray(matmul_pallas(a, b, relu=relu, num_buffers=1))
    for nb in (2, 4):
        y = np.asarray(matmul_pallas(a, b, relu=relu, num_buffers=nb))
        assert np.array_equal(y, ref), f"num_buffers={nb} diverged bitwise"
    if relu:
        assert (ref >= 0).all()


@pytest.mark.parametrize("ea,b,eb,nb,c,hh,wp,kh,kw,stride", [
    (2, 2, 2, 4, 3, 18, 32, 5, 5, 1),   # typical coded cell
    (2, 1, 2, 2, 1, 9, 9, 3, 3, 2),     # stride > 1, odd geometry
    (1, 2, 2, 3, 4, 16, 16, 3, 3, 1),   # degenerate ell_a = 1
    (3, 1, 1, 4, 2, 11, 13, 3, 5, 1),   # degenerate ell_b = 1, odd M/N/K
    (2, 2, 2, 4, 8, 10, 16, 1, 1, 1),   # 1x1 kernel, aligned K = 8
])
def test_worker_fused_vs_twostep_bit_parity(ea, b, eb, nb, c, hh, wp, kh,
                                            kw, stride):
    """In-kernel im2col and the two-step HBM-patch path are bit-identical:
    identical patch ordering (C, KH, KW) and identical fp32 chunk order."""
    from repro.kernels.conv2d.kernel import coded_worker_pallas

    xe = jnp.asarray(RNG.standard_normal((ea, b, c, hh, wp)), jnp.float32)
    ke = jnp.asarray(RNG.standard_normal((eb, nb, c, kh, kw)), jnp.float32)
    two = np.asarray(coded_worker_pallas(xe, ke, stride, fused_im2col=False))
    fused = np.asarray(coded_worker_pallas(xe, ke, stride, fused_im2col=True))
    assert np.array_equal(fused, two)
    ho = (hh - kh) // stride + 1
    if ho > 1:  # a split output-row tile must agree with the full-height one
        split = np.asarray(
            coded_worker_pallas(xe, ke, stride, fused_im2col=True, bo=1))
        assert np.array_equal(split, two)


def test_matmul_aligned_skips_padding():
    """Block-aligned operands take the no-copy path: no pad, no slice."""
    import jax

    from repro.kernels.matmul.kernel import matmul_pallas

    a = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((128, 256)), jnp.float32)
    text = jax.make_jaxpr(
        lambda a_, b_: matmul_pallas(a_, b_, num_buffers=2))(a, b).pretty_print()
    assert "pad" not in text and "slice" not in text
    # and an unaligned shape still pads (the guard is shape-specific)
    a2 = jnp.asarray(RNG.standard_normal((100, 100)), jnp.float32)
    b2 = jnp.asarray(RNG.standard_normal((100, 100)), jnp.float32)
    text2 = jax.make_jaxpr(
        lambda a_, b_: matmul_pallas(a_, b_, num_buffers=2))(a2, b2).pretty_print()
    assert "pad" in text2


@settings(max_examples=20, deadline=None)
@given(q=st.integers(2, 40), f=st.integers(1, 700), seed=st.integers(0, 99))
def test_coded_gemm_property(q, f, seed):
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.standard_normal((q, q)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((q, f)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(coded_gemm(c, t)), np.asarray(coded_gemm_ref(c, t)), atol=1e-3
    )


def test_crme_encode_decode_kernels_roundtrip():
    """Pallas encode -> decode recovers the tensor list exactly."""
    from repro.core.crme import make_axis_codes, recovery_matrix

    k_a, n = 4, 5
    a, b = make_axis_codes(k_a, 2, n)
    parts = jnp.asarray(RNG.standard_normal((k_a, 3, 6, 4)), jnp.float32)
    coded = crme_encode(parts, a.matrix)
    assert coded.shape == (2 * n, 3, 6, 4)
    # decode identity check on the A axis alone: solve A_sub^T y = coded_sub
    sub = [0, 1, 2, 3]  # 4 coded streams = k_a
    e = a.matrix[:, sub]
    d = np.linalg.inv(e.T)
    back = crme_decode(d, coded[jnp.asarray(sub)])
    np.testing.assert_allclose(np.asarray(back), np.asarray(parts), atol=1e-4)


@pytest.mark.parametrize("shape", [
    # (ea, b, c, hh, wp, eb, nb, kh, kw, stride)
    (2, None, 3, 14, 14, 2, 4, 3, 3, 1),   # multi-share, multi-group
    (2, 2, 8, 12, 16, 2, 8, 3, 3, 1),      # batched
    (1, None, 4, 17, 17, 1, 6, 5, 5, 2),   # strided, 5x5
    (3, 1, 16, 10, 10, 2, 16, 1, 1, 1),    # 1x1: widest channel windows
    (1, None, 2, 9, 9, 3, 5, 2, 2, 1),     # tiny odd geometry
])
def test_worker_stream_k_bit_parity(shape):
    """The K-streamed fused worker kernel (share in HBM, per-chunk channel
    windows double-buffered into VMEM) is bit-identical to the
    whole-share-resident fused kernel: same taps, same bk-chunk fp32
    accumulation order."""
    from repro.kernels.conv2d.kernel import coded_worker_pallas

    ea, b, c, hh, wp, eb, nb, kh, kw, stride = shape
    xshape = (ea, b, c, hh, wp) if b else (ea, c, hh, wp)
    xe = jnp.asarray(RNG.standard_normal(xshape), jnp.float32)
    ke = jnp.asarray(RNG.standard_normal((eb, nb, c, kh, kw)), jnp.float32)
    resident = coded_worker_pallas(xe, ke, stride, fused_im2col=True,
                                   stream_k=False)
    streamed = coded_worker_pallas(xe, ke, stride, stream_k=True)
    assert np.array_equal(np.asarray(resident), np.asarray(streamed))


def test_worker_stream_k_auto_fallback(monkeypatch):
    """When the whole share no longer fits the VMEM guard but the streamed
    buffers do, the fused path is kept via stream_k auto-fallback (instead
    of dropping to the two-step HBM-patch path) — and stays bit-identical
    to the resident result computed under the roomy guard."""
    import repro.kernels.conv2d.kernel as K

    c, hh, wp, kh = 64, 40, 40, 3
    xe = jnp.asarray(RNG.standard_normal((1, c, hh, wp)), jnp.float32)
    ke = jnp.asarray(RNG.standard_normal((1, 8, c, kh, kh)), jnp.float32)
    ho = wo = hh - kh + 1
    bo = K.default_bo(ho, wo)
    ref = K.coded_worker_pallas(xe, ke, 1, fused_im2col=True, stream_k=False)
    monkeypatch.setattr(K, "_FUSED_VMEM_ELEMS", 90_000)  # share = 215040
    assert not K._fused_feasible((1, c, hh, wp), kh, kh, 1, ho, wo, bo)
    assert K._stream_feasible((1, c, hh, wp), kh, kh, 1, ho, wo, bo, 128)
    auto = K.coded_worker_pallas(xe, ke, 1)  # picks the streamed fused path
    assert np.array_equal(np.asarray(ref), np.asarray(auto))


@pytest.mark.parametrize("stride", [1, 2, 4])
def test_space_to_depth_phase_planes(stride):
    """Phase-plane layout the fused kernel reads: element
    ``[g, a, b, (ph*s + pw)*C + c]`` is ``x[g, c, a*s + ph, b*s + pw]``,
    zero past the share's edge and in the (8, 128) tile padding."""
    from repro.kernels.conv2d.kernel import _space_to_depth

    g, c, hh, wp = 2, 3, 11, 13
    hs, ws = -(-hh // stride) + 1, -(-wp // stride) + 2
    x = RNG.standard_normal((g, c, hh, wp)).astype(np.float32)
    planes = np.asarray(_space_to_depth(jnp.asarray(x), stride, hs, ws))
    assert planes.shape[1] == hs
    assert planes.shape[2] % 8 == 0 and planes.shape[3] % 128 == 0
    want = np.zeros_like(planes)
    for ph in range(stride):
        for pw in range(stride):
            sub = x[:, :, ph::stride, pw::stride].transpose(0, 2, 3, 1)
            lo = (ph * stride + pw) * c
            want[:, :sub.shape[1], :sub.shape[2], lo:lo + c] = sub
    np.testing.assert_array_equal(planes, want)


@settings(max_examples=15, deadline=None)
@given(q=st.integers(2, 24), f=st.integers(1, 400), seed=st.integers(0, 99))
def test_coded_gemm_rebase_bit_parity(q, f, seed):
    """The multi-buffered ``matmul_pallas`` lowering of ``coded_gemm`` is
    bit-identical to the legacy feature-axis lowering: both contract the
    whole (tiny) code axis in one f32 dot, so the rebase changes schedule,
    never numerics."""
    from repro.kernels.coded_gemm.kernel import (coded_gemm_pallas,
                                                 coded_gemm_pallas_legacy)

    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.standard_normal((q, q)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((q, f)), jnp.float32)
    new = np.asarray(coded_gemm_pallas(c, t))
    old = np.asarray(coded_gemm_pallas_legacy(c, t))
    assert new.shape == old.shape == (q, f)
    assert np.array_equal(new, old), float(np.abs(new - old).max())
