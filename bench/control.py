"""Readings that a cell's correctness limit is set from, on the chip.

    python bench/control.py --workload alexnet-n8.poisson \\
        --seeds 1,2,3,...  --seconds 3 [--out bench/out/control.json]

In one process, for each seed: the cell is built and served at its own
load for a short window, exactly as ``run.py`` does, and every answer is
compared with the float32 ``HIGHEST`` reference.  That gives the program's
reading (the largest per-request relative error).  The control is the
reference itself put in the program's place, computed one precision lower
(``Precision.HIGH``: three bf16 passes): its reading is the largest
relative error of its answers for the same requests.  Both are judged by
the harness's own comparison (``runner.judge``), which has to find the
program correct and the control not.  The limit in the configuration file
sits between the largest program reading and the smallest control reading
(PERF.md).
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from harness import cell as cells  # noqa: E402
from harness import reference, runner, spec  # noqa: E402


def readings(c: spec.Cell, seed: int, seconds: float) -> dict:
    served = cells.ServedCell(c.config, c.traffic, seed)
    try:
        served.setup()
        w = served.window(seconds)
    finally:
        served.close()
    limit = float(c.config["limit_max_rel_err"])
    chk = served.check(w.sent)
    answered = [s for s in w.sent if s.ok()]
    ctl = reference.forward(c.config, served.params,
                            served.pool[np.asarray([s.image
                                                    for s in answered])],
                            precision="high")
    ctl_errs = reference.relative_errors(
        ctl, chk["reference"][[s.image for s in answered]])
    return {
        "seed": seed, "answers": int(len(chk["errors"])),
        "unanswered": chk["missing"], "compiles_in_window": len(w.compiles),
        "program_max": float(chk["errors"].max()),
        "program_median": float(np.median(chk["errors"])),
        "program_correct": runner.judge(chk["errors"], chk["missing"],
                                        len(w.compiles), limit)[0],
        "control_max": float(ctl_errs.max()),
        "control_median": float(np.median(ctl_errs)),
        # the control answers the same requests, all of them, in no window
        "control_correct": runner.judge(ctl_errs, 0, 0, limit)[0],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    c = spec.resolve(args.workload, ROOT)
    spec.enable_cache(ROOT)
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("control: needs a TPU")
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(c, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    summary = {
        "workload": args.workload,
        "lower": max(r["program_max"] for r in rows),
        "upper": min(r["control_max"] for r in rows),
        "limit": c.config["limit_max_rel_err"],
        "program_correct_all": all(r["program_correct"] for r in rows),
        "control_correct_any": any(r["control_correct"] for r in rows),
        "rows": rows,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
