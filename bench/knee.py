"""Find the highest open-loop rate a cell sustains: one sweep, one process.

    python bench/knee.py --workload alexnet-n8.poisson --seed 7 \\
        --rates 60,65,70,75 [--out bench/out/knee.json]

Builds the cell once, then for each rate sends that rate's Poisson
arrivals (the traffic file's other parameters unchanged) for a lead-in and
a window of the benchmark's ``run_seconds``.  A rate is sustained when
the window keeps up with it and the backlog does not grow: the requests
completed inside the window (whenever they were sent) number at least 98%
of those offered in it, and the median latency of the requests due in
the window's last quarter is at most 1.1 times that of its first
quarter.  The benchmark's open-loop cells run at 0.8 of the highest
sustained rate, written into their traffic file as a number.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from harness import cell as cells  # noqa: E402
from harness import spec  # noqa: E402

COMPLETED_SHARE = 0.98
GROWTH = 1.1


def reading(w, rate: float) -> dict:
    due = w.due()
    lat = cells.latencies_ms(w)
    q = w.seconds / 4
    first = [l for s, l in zip(due, lat) if s.due_t < w.t0 + q]
    last = [l for s, l in zip(due, lat) if s.due_t >= w.t1 - q]
    done = sum(1 for s in w.sent if s.ok() and w.t0 <= s.finish_t <= w.t1)
    r = {
        "offered_per_s": rate,
        "due": len(due),
        "completed_in_window_per_s": done / w.seconds,
        "p50_ms": float(np.percentile(lat, 50)),
        "p95_ms": float(np.percentile(lat, 95)),
        "p50_first_quarter_ms": float(np.median(first)),
        "p50_last_quarter_ms": float(np.median(last)),
        "mean_batch": w.stats.mean_batch_real,
        "compiles_in_window": len(w.compiles),
        "lateness_p95_ms": w.lateness_p95_s * 1e3,
    }
    r["sustained"] = bool(
        r["completed_in_window_per_s"] >= COMPLETED_SHARE * rate
        and r["p50_last_quarter_ms"] <= GROWTH * r["p50_first_quarter_ms"])
    return r


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated images/s, swept in this order")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    c = spec.resolve(args.workload, ROOT)
    seconds = float(spec.load_benchmark(ROOT)["run_seconds"])
    if c.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    spec.enable_cache(ROOT)
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("knee: needs a TPU")
    served = cells.ServedCell(c.config, c.traffic, args.seed)
    t0 = time.perf_counter()
    served.setup()
    print(f"knee: set-up {time.perf_counter() - t0:.1f} s", flush=True)
    rows = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            row = reading(served.window(seconds, rate_per_s=rate), rate)
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        served.close()
    ok = [r["offered_per_s"] for r in rows if r["sustained"]]
    summary = {"workload": args.workload, "seconds": seconds,
               "highest_sustained_per_s": max(ok) if ok else None,
               "rows": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
