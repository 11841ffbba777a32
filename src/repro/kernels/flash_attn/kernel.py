"""Flash attention as a Pallas TPU kernel.

The pure-JAX scan version in ``repro/models/transformer.py`` is what GSPMD
partitions across the mesh; on real TPU hardware this kernel replaces the
inner per-shard computation: grid (batch*heads, q_blocks, kv_blocks) with
the kv axis innermost, online-softmax state (m, l, acc) in VMEM scratch,
one HBM write per output tile.  Blocks are (bq, d)/(bk, d) with d padded
to the 128-lane register width by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.backend import interpret_kernels

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int,
                  sk_valid: int):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (bq, d)
    k = k_ref[0]  # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_idx < sk_valid  # padded key columns contribute nothing
    if causal:
        ok &= k_idx <= q_idx
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "bq", "bk", "interpret")
)
def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    scale: float | None = None,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q: (BH, Sq, D); k/v: (BH, Sk, D) -> (BH, Sq, D)."""
    interpret = interpret_kernels() if interpret is None else interpret
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    bq_ = min(bq, sq)
    bk_ = min(bk, sk)
    # pad sequence dims to block multiples; padded keys get masked by the
    # causal test (k_idx > any q_idx) or contribute exp(-inf)=0 via NEG_INF
    sq_p = -(-sq // bq_) * bq_
    sk_p = -(-sk // bk_) * bk_
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0)))
    nq, nk = sq_p // bq_, sk_p // bk_

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, bq=bq_, bk=bk_, nk=nk,
            sk_valid=sk,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :sq]
