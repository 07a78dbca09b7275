"""Benchmark driver — one module per paper table/figure (DESIGN.md §6).

Usage:
  PYTHONPATH=src python -m benchmarks.run                 # everything
  PYTHONPATH=src python -m benchmarks.run --only table1,pipeline
  PYTHONPATH=src python -m benchmarks.run --list

Prints ``name,us_per_call,derived`` CSV (one line per row) and appends the
full run to results/bench.csv. Measured rows carry real wall time; modeled
rows (roofline-derived, no TPU in this container) carry us_per_call=0 and
say so in ``derived``.
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time
import traceback

from repro.core.backend import configure_compile_cache

# Each entry: (short name, module, paper anchor)
BENCHES = [
    ("table1", "benchmarks.bench_table1", "Table I: model characteristics"),
    ("table2", "benchmarks.bench_table2", "Table II: op-level breakdown"),
    ("fig7", "benchmarks.bench_fig7", "Fig. 7: latency/QPS vs budget"),
    ("quant", "benchmarks.bench_quant", "SecV: quantization accuracy"),
    ("sls_balance", "benchmarks.bench_sls_balance",
     "SecVI-B: length-aware SLS balancing (15-34%)"),
    ("parallelize", "benchmarks.bench_parallelize",
     "SecVI-B: op parallelization (2.6x NLP)"),
    ("transfers", "benchmarks.bench_transfers",
     "SecVI-C: partial transfers + command batching"),
    ("pipeline", "benchmarks.bench_pipeline",
     "Fig. 6: pipelined sparse/dense execution"),
    ("serving", "benchmarks.bench_serving",
     "SecIV-C: unified serving runtime QPS/p95 (BENCH_serving.json)"),
    ("roofline", "benchmarks.roofline", "Roofline table from the dry-run"),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="results/bench.csv")
    args = ap.parse_args(argv)
    configure_compile_cache()

    if args.list:
        for name, mod, anchor in BENCHES:
            print(f"{name:14s} {mod:32s} {anchor}")
        return 0

    wanted = set(args.only.split(",")) if args.only else None
    all_rows, failures = [], []
    for name, mod_name, anchor in BENCHES:
        if wanted and name not in wanted:
            continue
        t0 = time.perf_counter()
        print(f"# === {name}: {anchor} ===", flush=True)
        try:
            mod = importlib.import_module(mod_name)
            rows = mod.run()
        except Exception:
            traceback.print_exc()
            failures.append(name)
            continue
        for r in rows:
            print(r.csv(), flush=True)
        all_rows.extend(rows)
        print(f"# ({time.perf_counter() - t0:.1f}s)", flush=True)

    if args.out and all_rows:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write("name,us_per_call,derived\n")
            for r in all_rows:
                f.write(r.csv() + "\n")
        print(f"# wrote {len(all_rows)} rows to {args.out}")
    if failures:
        print(f"# FAILED: {failures}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
