"""Experiment 10 (beyond paper): kernel roofline + persistent perf ledger.

Times the coded-worker kernel — the op the cluster launches n times per
layer per batch — on real (geometry, bucket) cells from ``plan_layers``
over the paper's CNNs, under three configurations:

  * ``baseline`` — the pre-PR kernel: two-step im2col (HBM patch tensor via
    ``conv_general_dilated_patches``) feeding the single-buffered grid-K
    ``matmul_pallas`` (``num_buffers=1``), default tiles.
  * ``fused``    — in-kernel im2col (``fused_im2col=True``): patch rows
    gathered inside the kernel, no HBM patch tensor, multi-buffered GEMM.
  * ``tuned``    — whatever the autotune ledger picks for the cell
    (``repro.kernels.autotune.tune_worker`` sweeps both strategies, so
    tuned is never a worse *choice* than either — modulo timing noise).

All three accumulate fp32 over identical K chunks in the same order, so
their outputs must be **bit-identical** (asserted, ``np.array_equal``).

Timing is interleaved and order-rotated (cf. exp9's paired timing): each
round times every variant once in rotating order, so clock drift on a
shared box cancels instead of biasing whichever ran last.

The perf trajectory persists in ``BENCH_kernels.json`` at the repo root
(committed): a plain run appends one dated run with per-cell
``{baseline_us, fused_us, tuned_us, speedup}``.  ``--smoke`` is the CI
gate and is read-only: it asserts (a) fused beats baseline on every cell,
(b) bit-identical outputs, and (c) the fresh fused-vs-baseline speedup of
every cell is no worse than 10% below the last committed run for that
cell — a kernel regression fails CI even if everything stays "correct".

  PYTHONPATH=src python -m benchmarks.exp10_kernel_roofline          # append
  PYTHONPATH=src python -m benchmarks.exp10_kernel_roofline --smoke  # CI gate
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import interpret_kernels
from repro.core.fcdcc import CodedConv2d
from repro.core.pipeline import plan_layers
from repro.kernels import autotune
from repro.kernels.conv2d.kernel import coded_worker_pallas
from repro.models.cnn import CNN_SPECS, input_hw

from .common import emit

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_kernels.json")
VARIANTS = ("baseline", "fused", "tuned")
REGRESSION_TOL = 0.9  # fresh speedup must stay >= 0.9x the committed one


def _middle_spec(arch: str, n: int, kab):
    hw0, layers = CNN_SPECS[arch]
    specs = plan_layers(layers, input_hw(arch, smoke=True), n,
                        default_kab=kab)
    return specs[len(specs) // 2]


def interleaved(fns: dict, repeat: int = 5) -> dict:
    """min-of-N seconds per named thunk, one call of each per round in
    rotating order (exp9's paired timing generalized to N variants)."""
    names = list(fns)
    for name in names:  # compile + warm outside the timed region
        jax.block_until_ready(fns[name]())
    ts = {name: [] for name in names}
    for i in range(repeat):
        order = names[i % len(names):] + names[:i % len(names)]
        for name in order:
            t0 = time.perf_counter()
            jax.block_until_ready(fns[name]())
            ts[name].append(time.perf_counter() - t0)
    return {name: min(v) for name, v in ts.items()}


def time_cell(spec, batch: int, rng, repeat: int = 5):
    """Seconds per variant for one worker subtask cell + bit-parity check."""
    geo = spec.geo
    x = jnp.asarray(rng.standard_normal(
        (batch, geo.in_channels, geo.height, geo.width)), jnp.float32)
    k = jnp.asarray(rng.standard_normal(
        (geo.out_channels, geo.in_channels, geo.kernel_h, geo.kernel_w)),
        jnp.float32)
    enc = CodedConv2d(spec.plan, spec.geo, backend="lax")
    xe = jax.block_until_ready(enc.encode_inputs(x)[0])
    ke = jax.block_until_ready(enc.encode_filters(k)[0])
    stride = geo.stride
    tuned_kw = autotune.tune_worker(tuple(xe.shape), tuple(ke.shape), stride)
    configs = {
        "baseline": {"fused_im2col": False, "num_buffers": 1},
        "fused": {"fused_im2col": True},
        "tuned": tuned_kw,
    }
    fns, outs = {}, {}
    for name, kw in configs.items():
        fn = jax.jit(lambda a, b, kw_=dict(kw): coded_worker_pallas(
            a, b, stride, **kw_))
        outs[name] = np.asarray(jax.block_until_ready(fn(xe, ke)))
        fns[name] = lambda fn_=fn: fn_(xe, ke)
    for name in ("fused", "tuned"):  # same fp32 chunk order -> bit-identical
        assert np.array_equal(outs[name], outs["baseline"]), (
            f"{name} output differs bitwise from baseline for {spec.name}")
    return interleaved(fns, repeat=repeat), tuned_kw


def load_bench(path: str = BENCH_PATH) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return {"schema": 1, "runs": []}


def committed_speedups(bench: dict) -> dict:
    """Per-cell fused-vs-baseline speedup of the most recent committed run
    that measured the cell."""
    out = {}
    for run in bench["runs"]:
        for cell, rec in run.get("cells", {}).items():
            out[cell] = rec["speedup"]
    return out


def run(quick: bool = True, smoke: bool = False, update: bool = True):
    archs = ("lenet5", "alexnet") if quick else ("lenet5", "alexnet", "vgg16")
    buckets = (1, 4) if quick else (1, 4, 8)
    n, kab = 8, (2, 4)
    rng = np.random.default_rng(0)
    prior = committed_speedups(load_bench())
    cells, failures, regressions = {}, [], []
    for arch in archs:
        spec = _middle_spec(arch, n, kab)
        for batch in buckets:
            ts, tuned_kw = time_cell(spec, batch, rng)
            cell = f"{arch}/{spec.name}/b{batch}"
            speedup = ts["baseline"] / ts["fused"]
            cells[cell] = {
                "baseline_us": round(ts["baseline"] * 1e6, 1),
                "fused_us": round(ts["fused"] * 1e6, 1),
                "tuned_us": round(ts["tuned"] * 1e6, 1),
                "speedup": round(speedup, 3),
            }
            for name in VARIANTS:
                emit(f"exp10/{cell}/{name}", ts[name],
                     f"fused_vs_baseline={speedup:.2f}x "
                     f"tuned={tuned_kw}")
            if speedup <= 1.0:
                failures.append((cell, round(speedup, 3)))
            committed = prior.get(cell)
            if committed and speedup < REGRESSION_TOL * committed:
                regressions.append((cell, round(speedup, 3), committed))
    if smoke:
        if failures:
            raise SystemExit(
                f"fused kernel did not beat the baseline: {failures}")
        if regressions:
            raise SystemExit(
                "kernel perf regressed >10% vs the committed BENCH "
                f"trajectory (cell, now, committed): {regressions}")
        return cells
    if update:
        bench = load_bench()
        bench["runs"].append({
            "date": time.strftime("%Y-%m-%d"),
            "backend": jax.default_backend(),
            "interpret": interpret_kernels(),
            "quick": quick,
            "cells": cells,
        })
        tmp = f"{BENCH_PATH}.tmp"
        with open(tmp, "w") as f:
            json.dump(bench, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, BENCH_PATH)
    return cells


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all three CNNs + bucket 8")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: assert fused beats baseline bit-exactly "
                         "and no >10%% regression vs BENCH_kernels.json "
                         "(read-only)")
    ap.add_argument("--no-update", action="store_true",
                    help="measure + print only; don't append to the ledger")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(quick=not args.full, smoke=args.smoke, update=not args.no_update)
