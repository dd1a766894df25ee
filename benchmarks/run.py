"""Benchmark entry point: one module per paper table/figure + kernel and
throughput microbenches.  Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only table3_glue,kernel]
  REPRO_BENCH_SCALE=paper  for full-size runs (hours).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.runtime.compile_cache import use_compile_cache

MODULES = [
    ("table1_mnli", "benchmarks.table1_mnli"),
    ("table2_mrpc", "benchmarks.table2_mrpc"),
    ("table3_glue", "benchmarks.table3_glue"),
    ("table4_ablation", "benchmarks.table4_ablation"),
    ("fig1_tradeoff", "benchmarks.fig1_tradeoff"),
    ("kernel", "benchmarks.kernel_bench"),
    ("train_throughput", "benchmarks.train_throughput"),
    ("serve_multitenant", "benchmarks.serve_multitenant"),
    ("multi_replica", "benchmarks.multi_replica"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument(
        "--json-out", default=None,
        help="write {name: us_per_call} JSON (e.g. BENCH_smoke.json) on top "
        "of the CSV rows; written even when a benchmark fails",
    )
    args = ap.parse_args()
    use_compile_cache()
    sel = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = []
    for name, mod in MODULES:
        if sel and name not in sel:
            continue
        t0 = time.time()
        try:
            __import__(mod, fromlist=["main"]).main()
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()[-1500:]}", flush=True)
    if args.json_out:
        from benchmarks import common

        common.write_json(args.json_out)
        print(f"# wrote {args.json_out}")
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
