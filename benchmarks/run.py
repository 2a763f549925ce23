"""Benchmark harness entry point: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig7,table3]

Emits ``name,us_per_call,derived`` CSV rows (harness contract), prints
human-readable tables, writes JSON artifacts under results/bench/, and
finishes with the roofline summary derived from the dry-run artifacts
(if present).

REPRO_BENCH_BUDGET=full enlarges training budgets (default: small/CPU).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

BENCHES = [
    ("kernel_microbench", "benchmarks.kernel_microbench"),
    ("filter_latency", "benchmarks.filter_latency"),
    ("fig7_count_accuracy", "benchmarks.fig7_count_accuracy"),
    ("fig11_ccf", "benchmarks.fig11_ccf"),
    ("fig15_clf", "benchmarks.fig15_clf"),
    ("table3_query_speedup", "benchmarks.table3_query_speedup"),
    ("table4_cv_variance", "benchmarks.table4_cv_variance"),
    ("multi_query_sharing", "benchmarks.multi_query_sharing"),
    ("query_churn", "benchmarks.query_churn"),
    ("aggregate_contracts", "benchmarks.aggregate_contracts"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark name filter")
    args = ap.parse_args()
    only = [s for s in args.only.split(",") if s]

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    print("name,us_per_call,derived")
    failures = []
    for name, mod_name in BENCHES:
        if only and not any(o in name for o in only):
            continue
        t0 = time.time()
        print(f"\n=== {name} ===", flush=True)
        try:
            import importlib
            mod = importlib.import_module(mod_name)
            mod.run()
            print(f"[{name}] done in {time.time()-t0:.0f}s", flush=True)
        except Exception as e:
            failures.append(name)
            print(f"[{name}] FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()

    # roofline summary (reads dry-run artifacts if the sweep has run)
    try:
        import os
        if os.path.isdir("results/dryrun"):
            from benchmarks import roofline
            recs = roofline.load("results/dryrun")
            if recs:
                print("\n=== roofline (from dry-run artifacts) ===")
                print(roofline.table(recs, "single"))
    except Exception as e:
        print(f"[roofline] skipped: {e}")

    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        sys.exit(1)
    print("\nall benchmarks complete.")


if __name__ == "__main__":
    main()
