"""The paper reproductions: one experiment per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  These are plain scripts
with no speed assertion; serving speed is measured only by
``bench/run.py`` (``BENCHMARK.json``) on the chip.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only exp1,exp5]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes (slow)")
    ap.add_argument("--only", default=None, help="comma list: exp1..exp5")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch size for the coded-pipeline sections (exp1/exp4)")
    args = ap.parse_args()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None

    from repro.backend import enable_compile_cache

    enable_compile_cache()

    from . import (
        exp1_naive_vs_fcdcc,
        exp2_stability,
        exp3_scalability,
        exp4_stragglers,
        exp5_partition_opt,
    )

    experiments = {
        "exp1": lambda quick: exp1_naive_vs_fcdcc.run(quick, batch=args.batch),
        "exp2": exp2_stability.run,
        "exp3": exp3_scalability.run,
        "exp4": lambda quick: exp4_stragglers.run(quick, batch=args.batch),
        "exp5": exp5_partition_opt.run,
    }
    print("name,us_per_call,derived")
    failed = []
    for name, fn in experiments.items():
        if only and name not in only:
            continue
        try:
            fn(quick=quick)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
