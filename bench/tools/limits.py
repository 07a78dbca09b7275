#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: for each seed, the number
that a run compares (the program's) beside the same number for the control
(the reference computed one precision lower, in the program's place), in
one process so that set-up is paid once per seed and compiles once.

    python3 bench/tools/limits.py --workload ds7b-chat --seconds 15 \\
        --seeds 1 2 3 ... [--control-seeds 1 2 3]

Each seed makes its own weights or tables, serves a window at the cell's
own load, and is checked as a run checks it. Prints one JSON line per seed.
"""
import argparse
import gc
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None)
    args = ap.parse_args()
    control = set(args.seeds if args.control_seeds is None
                  else args.control_seeds)
    bench, cell, cfg, traffic = run.load_cell(ROOT, args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    devs, peaks = run.device_check(jax, cell["chips"], ("tpu",),
                                   run.BENCH / "peaks.json")
    drv = importlib.import_module(f"bench.drivers.{cfg['kind']}")
    for seed in args.seeds:
        t = time.perf_counter()
        ctx = run.Ctx(ROOT, cell, cfg, traffic, seed, args.seconds,
                      cell["chips"], time.time(), peaks)
        c = drv.Cell(ctx)
        c.build(seed)
        c.warm()
        win = c.window(seed, args.seconds)
        c.free()
        chk = c.check(seed, win, control=seed in control)
        out = {"seed": seed, "compiles": win["compiles"],
               "seconds": time.perf_counter() - t, **chk}
        print(json.dumps(out), flush=True)
        del c, win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
