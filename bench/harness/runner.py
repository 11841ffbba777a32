"""One run of one cell: set-up, window, reference check, result line."""
from __future__ import annotations

import json
import math
import os
import shutil
import time

import numpy as np

from . import spec as specs
from .cell import (ServedCell, device_info, end_to_end, log, out_dir,
                   window_record)


def load_peaks(root: str, device_kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def _finite(v):
    return v if v is not None and math.isfinite(v) else None


def judge(errors, missing: int, compiles: int, limit: float):
    """The comparison that decides ``correct``: every answer within the
    configuration's relative-error limit of the reference, none missing,
    and no program compiled or loaded inside the window.  Returns
    ``(correct, checks)``, each check a number beside its limit."""
    worst = float(np.max(errors)) if len(errors) else math.inf
    checks = {
        "max_rel_err": {"value": _finite(worst), "limit": limit},
        "unanswered": {"value": missing, "limit": 0},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }
    correct = bool(len(errors) > 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values()))
    return correct, checks


def run(cell: specs.Cell, seed: int, seconds: float, trace: bool, *,
        process_start: float, root: str = specs.ROOT, devices=None) -> dict:
    import jax

    devices = devices if devices is not None else jax.devices()
    kind = devices[0].device_kind
    peaks = load_peaks(root, kind) if devices[0].platform == "tpu" else None
    served = ServedCell(cell.config, cell.traffic, seed)
    trace_dir = None
    if trace:
        trace_dir = out_dir(root, "trace", cell.name)
        shutil.rmtree(trace_dir)
    try:
        served.setup()
        t_ready = time.perf_counter()
        w = served.window(seconds, trace_dir=trace_dir)
        device = device_info(devices, cell.chips)
    finally:
        served.close()
    setup_s = w.t0 - process_start
    log(f"bench: {cell.name} seed {seed}: set-up {setup_s:.3f} s "
        f"(server ready after {t_ready - process_start:.3f} s); stragglers "
        f"{served.stragglers}; {len(w.sent)} requests sent, "
        f"{len(w.due())} due in the {w.seconds:.3f} s window; "
        f"{len(w.compiles)} backend compilations in the window "
        f"{w.compiles}; generator "
        f"p95 lateness {w.lateness_p95_s * 1e3:.3f} ms")
    if w.stats is not None and w.overlap is not None:
        log(f"bench: window stats {w.stats.summary_line()}; rounds "
            f"{w.overlap.rounds}")
    t_ref = time.perf_counter()
    chk = served.check(w.sent)
    log(f"bench: reference over {len(chk['reference'])} images and the "
        f"comparison took {time.perf_counter() - t_ref:.3f} s")

    metrics, breakdown = {}, None
    if trace:
        rec = window_record(served, w, peaks)
        for m in cell.per_layer:
            v = specs.metric_reader(m["name"], root)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if w.trace is not None:
            device["busy_s"] = w.trace["busy_s"]
            device["window_s"] = w.trace["window_s"]
            breakdown = {"device_ops": [[n, s] for n, s in
                                        w.trace["top_ops"]],
                         "idle_gaps": w.trace["idle_gaps"]}
            log(f"bench: device programs (s) {w.trace['programs']}; runs "
                f"{w.trace['program_runs']}; worker runs by (layer, batch) "
                f"{w.trace['worker_runs']}, unmapped "
                f"{w.trace['worker_unmapped']}")
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {
                "value": _finite(end_to_end(m["name"], w, setup_s)),
                "unit": m["unit"]}

    due = w.due()
    errors = chk["errors"]
    correct, checks = judge(errors, chk["missing"], len(w.compiles),
                            float(cell.config["limit_max_rel_err"]))
    result = {
        "correct": correct,
        "attempted": len(due),
        "failed": sum(1 for s in due if not s.ok()),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    log(f"bench: {len(errors)} answers compared, median relative error "
        f"{float(np.median(errors)) if len(errors) else math.nan:.3e}")
    result["checks"] = checks
    return result
