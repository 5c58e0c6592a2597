"""Benchmark harness entry point — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).

  PYTHONPATH=src python -m benchmarks.run            # all paper benchmarks
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced sizes
  PYTHONPATH=src python -m benchmarks.run --json out/   # also BENCH_*.json
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="also write machine-readable BENCH_<name>.json "
                         "(per-row us_per_call) into DIR")
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (ablation_o123, common, density_analysis,
                            end_to_end, format_crossover, fused,
                            granularity_baselines, memory_overhead,
                            minibatch, overhead, robustness, serving)

    scale = 0.04 if args.quick else 0.08
    jobs = {
        "fused_transform_aggregate": lambda: fused.run(
            n=1024 if args.quick else 2048,
            e=12000 if args.quick else 30000,
            fin=32 if args.quick else 64,
            fout=256 if args.quick else 512),
        "fig2b_format_crossover": lambda: format_crossover.run(
            n=512 if args.quick else 1024),
        "fig4_density_analysis": lambda: density_analysis.run(
            scale=0.03 if args.quick else 0.05),
        "fig8_end_to_end": lambda: end_to_end.run(
            scale=0.05 if args.quick else 0.1,
            steps=5 if args.quick else 8),
        "fig9_10_granularity": lambda: granularity_baselines.run(scale=scale),
        "fig11_ablation_o123": lambda: ablation_o123.run(scale=scale),
        "sec6_3_overhead": lambda: overhead.run(
            scale=0.05 if args.quick else 0.1,
            steps=10 if args.quick else 20),
        "minibatch_sampling": lambda: minibatch.run(
            scale=0.04 if args.quick else 0.05,
            steps=15 if args.quick else 25),
        "robustness": lambda: robustness.run(
            scale=0.03 if args.quick else 0.04,
            steps=9 if args.quick else 12),
        "serving": lambda: serving.run(
            scale=0.1 if args.quick else 0.15,
            train_steps=6 if args.quick else 8,
            seconds=0.6 if args.quick else 1.0),
        "fig12_memory_overhead": lambda: memory_overhead.run(),
    }
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for name, job in jobs.items():
        if only and name not in only:
            continue
        common.drain_records()
        try:
            job()
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},NaN,FAILED")
        if args.json:
            common.write_bench_json(name, common.drain_records(), args.json)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
