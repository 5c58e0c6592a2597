#!/usr/bin/env python3
"""Readings of a cell's control and planted faults, at the cell's size.

  python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed, prints one JSON line per variant with the numbers the cell
compares (bench/compare.py): the control is the reference with float8
matmul inputs in the program's place (bench/reference.py); the faults are
those the cell can have.
The limits in bench/workloads/<cell>.json sit between these readings and
the program's own.  Needs a TPU unless ``--rehearse`` is given.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = bench_run.resolve(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("control: no TPU", file=sys.stderr)
        return 2
    if not args.rehearse:
        from repro import compile_cache
        compile_cache.enable()
    mode = bench_run._module(bench_run.BENCH / "modes"
                             / f"{cell['traffic']['mode']}.py")
    for seed in args.seeds:
        ns = argparse.Namespace(seed=seed, seconds=1.0, trace=0,
                                rehearse=args.rehearse)
        ctx = bench_run.Context(cell, ns, None)
        for variant, checks in mode.control(ctx).items():
            print(json.dumps(dict(workload=args.workload, seed=seed,
                                  variant=variant, **checks)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
