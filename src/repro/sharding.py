"""Mesh-aware sharding hints.

``shard_hint(x, spec...)`` applies ``with_sharding_constraint`` only when a
mesh is active (jax.set_mesh context), choosing per-dim mesh axes from the
candidates that (a) exist in the current mesh and (b) divide the dim —
so the same model code runs on 1 CPU device, a 16x16 pod, or a 2x16x16
multi-pod mesh without edits (smollm's 9 heads simply fall back to
replication, etc.).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P


# canonical logical axes
BATCH = ("pod", "data")  # batch (or sequence for long-context) shards here
MODEL = "model"
WORKERS = "workers"  # the coded cluster's n-worker axis (1-D worker mesh)

__all__ = ["shard_hint", "BATCH", "MODEL", "WORKERS", "resolve_pspec",
           "worker_devices"]


def worker_devices(mesh, n: int) -> list:
    """The n coded workers' device pinning, derived from a worker mesh
    (``launch.mesh.make_worker_mesh``): worker ``i`` runs on device
    ``i % mesh_size``.  With fewer physical devices than workers the
    round-robin oversubscribes evenly (the 1-device degenerate case pins
    everything to that device — functionally the thread pool's layout);
    with ``mesh_size >= n`` every worker owns its device exclusively."""
    devs = list(mesh.devices.flat)
    if not devs:
        raise ValueError("empty mesh")
    return [devs[i % len(devs)] for i in range(n)]


def _resolve_dim(dim: int, cand, mesh_shape) -> tuple[str, ...] | None:
    if cand is None:
        return None
    if isinstance(cand, str):
        cand = (cand,)
    chosen = tuple(a for a in cand if a in mesh_shape)
    if not chosen:
        return None
    total = math.prod(mesh_shape[a] for a in chosen)
    if total and dim % total == 0:
        return chosen
    # try single best axis
    for a in chosen:
        if dim % mesh_shape[a] == 0:
            return (a,)
    return None


def resolve_pspec(shape, axes, mesh_shape) -> P:
    out = []
    used: set[str] = set()
    for dim, cand in zip(shape, axes):
        r = _resolve_dim(dim, cand, mesh_shape)
        if r is None or any(a in used for a in r):
            out.append(None)
        else:
            used.update(r)
            out.append(r if len(r) > 1 else r[0])
    return P(*out)


def shard_hint(x, *axes):
    """Constrain ``x`` (rank == len(axes)) if a mesh is active.

    Each entry of ``axes`` is None, an axis name, or a tuple of candidate
    axis names to use jointly (e.g. ``BATCH`` = ("pod", "data")).
    """
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return x
    spec = resolve_pspec(x.shape, axes, dict(am.shape))
    return jax.lax.with_sharding_constraint(x, spec)
