"""Kernel autotuner: per-cell tile/buffer sweeps with a persistent ledger.

The coded hot path runs two Pallas kernel families — the worker's
implicit-GEMM conv (``coded_worker_pallas``) and the transition/decode
GEMMs (``matmul_pallas``) — whose best (block sizes, buffer depth, im2col
strategy) depend on the (geometry, batch-bucket) cell: skinny decode GEMMs
want wide N blocks, small-share conv cells want the two-step im2col, big
shares want the in-kernel one.  This module sweeps a bounded candidate set
per cell, caches the winner in a JSON ledger keyed by
``kind/backend/interpret/shape``, and exposes trace-time lookups that the
ops layer consults when a jitted program is built.

Contract with the bounded-program guarantee: **lookups never sweep**.  A
sweep runs only through the explicit ``tune_*`` entry points (called by
``CodedPipeline.autotune_kernels`` and ``benchmarks/exp10_kernel_roofline``);
a cache miss at trace time just returns None and the kernel uses its
defaults.  Tile sizes are static kernel arguments, so a tuned program is
the same single trace per (geometry, bucket) an untuned one would be.

The ledger lives at ``kernels/autotune_ledger.json`` next to this module
by default — a file git commits, so a run takes tuned tiles only from a
ledger that was committed with the code (no such file: every kernel uses
its default tiles).  Override with ``REPRO_AUTOTUNE_CACHE`` or the ``path``
arguments.
"""
from __future__ import annotations

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import interpret_kernels

__all__ = [
    "cache_path", "clear_cache", "load_cache", "save_cache", "sweep_count",
    "matmul_key", "worker_key", "matmul_params", "worker_params",
    "tune_matmul", "tune_worker",
]

_LOCK = threading.RLock()
# key -> {"params": {...}, "us": float, ...}  # guarded-by: _LOCK
_CACHE: dict | None = None
# how many real sweeps ran (tests assert cache hits skip them)  # guarded-by: _LOCK
_SWEEPS = 0

# Bounded candidate sets: every candidate is a full static-arg tuple, so a
# sweep costs len(candidates) extra jit traces ONCE per cell, never per run.
MATMUL_CANDIDATES: tuple[dict, ...] = (
    {"bm": 128, "bn": 128, "bk": 128, "num_buffers": 1},
    {"bm": 128, "bn": 128, "bk": 128, "num_buffers": 2},
    {"bm": 128, "bn": 128, "bk": 128, "num_buffers": 4},
    {"bm": 128, "bn": 512, "bk": 128, "num_buffers": 2},
    {"bm": 256, "bn": 128, "bk": 256, "num_buffers": 2},
)


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "autotune_ledger.json"),
    )


def _backend_tag(interpret: bool | None) -> str:
    if interpret is None:
        interpret = interpret_kernels()
    return f"{jax.default_backend()}/interpret={int(bool(interpret))}"


def matmul_key(m: int, k: int, n: int, *, relu: bool = False,
               interpret: bool | None = None) -> str:
    return (f"matmul/{_backend_tag(interpret)}/"
            f"m{m}k{k}n{n}/relu={int(bool(relu))}")


def worker_key(xe_shape: tuple, ke_shape: tuple, stride: int) -> str:
    """Cell key for one worker subtask: coded-share and filter-group shapes
    (the batch dim rides inside ``xe_shape``, so buckets key separately)."""
    xs = "x".join(map(str, xe_shape))
    ks = "x".join(map(str, ke_shape))
    return f"worker/{_backend_tag(None)}/xe{xs}/ke{ks}/s{stride}"


# -- ledger ----------------------------------------------------------------
def load_cache(path: str | None = None, *, reload: bool = False) -> dict:
    """The in-memory ledger, loading the JSON file on first touch."""
    global _CACHE
    with _LOCK:
        if _CACHE is None or reload:
            p = path or cache_path()
            try:
                with open(p) as f:
                    _CACHE = json.load(f)
            except (FileNotFoundError, json.JSONDecodeError):
                _CACHE = {}
        return _CACHE


def save_cache(path: str | None = None) -> str:
    p = path or cache_path()
    with _LOCK:
        cache = load_cache(path)
        os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
        tmp = f"{p}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, p)  # atomic: concurrent readers never see a torn file
    return p


def clear_cache(*, memory_only: bool = False, path: str | None = None) -> None:
    """Drop the in-memory ledger (and the JSON file unless ``memory_only``)."""
    global _CACHE, _SWEEPS
    with _LOCK:
        _CACHE = None
        _SWEEPS = 0
        if not memory_only:
            try:
                os.remove(path or cache_path())
            except FileNotFoundError:
                pass


def sweep_count() -> int:
    """Real sweeps run since import/clear — the cache-hit test hook."""
    return _SWEEPS


def _lookup(key: str) -> dict | None:
    entry = load_cache().get(key)
    return dict(entry["params"]) if entry else None


def _record(key: str, params: dict, us: float, swept: list, path=None) -> None:
    global _SWEEPS
    with _LOCK:
        _SWEEPS += 1
        load_cache(path)[key] = {
            "params": params,
            "us": round(us, 2),
            "swept": swept,
        }
        save_cache(path)


# -- trace-time lookups (never sweep) --------------------------------------
def matmul_params(m: int, k: int, n: int, *, relu: bool = False,
                  interpret: bool | None = None) -> dict | None:
    """Tuned ``matmul_pallas`` kwargs for this GEMM cell, or None."""
    return _lookup(matmul_key(m, k, n, relu=relu, interpret=interpret))


def worker_params(xe_shape: tuple, ke_shape: tuple,
                  stride: int) -> dict | None:
    """Tuned ``coded_worker_pallas`` kwargs for this worker cell, or None."""
    return _lookup(worker_key(xe_shape, ke_shape, stride))


# -- timing ----------------------------------------------------------------
def _time_best(fn, args, repeat: int) -> float:
    jax.block_until_ready(fn(*args))  # compile outside the timed region
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts) * 1e6


# -- sweeps ----------------------------------------------------------------
def tune_matmul(m: int, k: int, n: int, *, relu: bool = False,
                dtype=jnp.float32, candidates=None, repeat: int = 3,
                force: bool = False, path: str | None = None) -> dict:
    """Sweep ``matmul_pallas`` configs for an (m, k, n) cell; cache winner.

    Returns the winning kwargs.  A cached cell returns instantly without
    sweeping unless ``force``.
    """
    key = matmul_key(m, k, n, relu=relu)
    if not force:
        hit = _lookup(key)
        if hit is not None:
            return hit
    from repro.kernels.matmul.kernel import matmul_pallas

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    swept = []
    best, best_us = None, float("inf")
    for cand in candidates or MATMUL_CANDIDATES:
        us = _time_best(
            lambda a_, b_, c=dict(cand): matmul_pallas(
                a_, b_, relu=relu, **c),
            (a, b), repeat,
        )
        swept.append({"params": dict(cand), "us": round(us, 2)})
        if us < best_us:
            best, best_us = dict(cand), us
    _record(key, best, best_us, swept, path)
    return best


def worker_candidates(xe_shape: tuple, ke_shape: tuple,
                      stride: int) -> list[dict]:
    """Candidate set for a worker cell: the in-kernel-im2col kernel over a
    few output-row tiles, plus the two-step path over buffer depths."""
    from repro.kernels.conv2d.kernel import default_bo

    kh, kw = ke_shape[-2:]
    hh, wp = xe_shape[-2:]
    ho = (hh - kh) // stride + 1
    wo = (wp - kw) // stride + 1
    bos = sorted({default_bo(ho, wo), ho, default_bo(ho, wo, target=64)})
    cands = [{"fused_im2col": True, "bo": bo} for bo in bos if ho % bo == 0]
    cands += [
        {"fused_im2col": False, "num_buffers": 2},
        {"fused_im2col": False, "num_buffers": 4},
    ]
    return cands


def tune_worker(xe_shape: tuple, ke_shape: tuple, stride: int, *,
                dtype=jnp.float32, candidates=None, repeat: int = 3,
                force: bool = False, path: str | None = None) -> dict:
    """Sweep the coded-worker kernel for one (shapes, stride) cell.

    ``xe_shape``: one worker's coded input shares ``(ell_a, [B,] C, h_hat,
    Wp)``; ``ke_shape``: its filter groups ``(ell_b, N/k_b, C, KH, KW)``.
    The sweep covers both im2col strategies, so the tuned path is never
    slower than either default.
    """
    key = worker_key(xe_shape, ke_shape, stride)
    if not force:
        hit = _lookup(key)
        if hit is not None:
            return hit
    from repro.kernels.conv2d.kernel import coded_worker_pallas

    rng = np.random.default_rng(0)
    xe = jnp.asarray(rng.standard_normal(xe_shape), dtype)
    ke = jnp.asarray(rng.standard_normal(ke_shape), dtype)
    swept = []
    best, best_us = None, float("inf")
    for cand in candidates or worker_candidates(xe_shape, ke_shape, stride):
        fn = jax.jit(
            lambda x, k, c=dict(cand): coded_worker_pallas(
                x, k, stride, **c)
        )
        us = _time_best(fn, (xe, ke), repeat)
        swept.append({"params": dict(cand), "us": round(us, 2)})
        if us < best_us:
            best, best_us = dict(cand), us
    _record(key, best, best_us, swept, path)
    return best
