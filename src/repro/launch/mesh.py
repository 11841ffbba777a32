"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the "pod"
axis carries data parallelism across the inter-pod (DCN-ish) links; the
gradient-compression path in the train step targets exactly that axis.

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh():
    """1-device mesh for tests/examples on this CPU container."""
    return jax.make_mesh(
        (1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def make_worker_mesh(n: int, devices=None):
    """1-D ``("workers",)`` mesh for the coded cluster's device pool.

    Uses ``devices`` when given, else every addressable device — capped at
    ``n`` (a 6-worker cluster on an 8-device host leaves 2 devices free for
    the master / other tenants).  Fewer devices than workers is fine: the
    pool round-robins workers over the mesh (``sharding.worker_devices``),
    down to the 1-device degenerate case CI's default host exposes.  On a
    ``--xla_force_host_platform_device_count=8`` host (or a real TPU/GPU
    slice) each worker gets its own compute queue.
    """
    if n < 1:
        raise ValueError(f"need n >= 1 workers, got {n}")
    devs = list(devices) if devices is not None else list(jax.devices())
    devs = devs[:n]
    return jax.make_mesh(
        (len(devs),), ("workers",),
        axis_types=(jax.sharding.AxisType.Auto,), devices=devs,
    )


# TPU v5e-ish hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link (we charge the full collective wire bytes
#               against one link — the bottleneck-link model)
