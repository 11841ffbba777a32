"""Public wrapper: (B, S, H, D) layout -> kernel's (B*H, S, D)."""
import jax.numpy as jnp

from .kernel import flash_attention_pallas

__all__ = ["flash_attention"]


def flash_attention(q, k, v, *, scale=None, causal=True, **kw):
    """q: (B, Sq, H, D); k/v: (B, Sk, H, D) (GQA pre-repeated)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    out = flash_attention_pallas(qf, kf, vf, scale=scale, causal=causal, **kw)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
