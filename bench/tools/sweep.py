#!/usr/bin/env python3
"""Offer an LM cell's mix at several fixed rates in one process, to find the
highest rate it sustains (the knee). One set-up serves every rate; the
engine drains between windows.

    python3 bench/tools/sweep.py --workload ds7b-chat --seed 7 --seconds 20 \\
        --rates 2 3 4 5 6

Prints one JSON line per rate: offered and completed requests per second,
TTFT and inter-token percentiles, and how many requests were still
unanswered at the close (a backlog that grows with the window's length
means the rate is above the knee).
"""
import argparse
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    bench, cell, cfg, traffic = run.load_cell(ROOT, args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    devs, peaks = run.device_check(jax, cell["chips"], ("tpu",),
                                   run.BENCH / "peaks.json")
    from bench.drivers import lm
    ctx = run.Ctx(ROOT, cell, cfg, traffic, args.seed, args.seconds,
                  cell["chips"], time.time(), peaks)
    c = lm.Cell(ctx)
    c.build(args.seed)
    c.warm()
    for rate in args.rates:
        c.mix = dict(traffic, arrivals=dict(traffic["arrivals"],
                                            rate_per_s=rate))
        win = c.window(args.seed, args.seconds)
        e2e = lm.end_to_end(win)
        out = {"rate_per_s": rate, "compiles": win["compiles"],
               "unanswered": e2e["attempted"] - e2e["completed"],
               **{k: v for k, v in e2e.items()}}
        print(json.dumps(out), flush=True)
        while c.target.has_work:
            c.target.tick()
    return 0


if __name__ == "__main__":
    sys.exit(main())
