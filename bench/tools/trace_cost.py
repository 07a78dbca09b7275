#!/usr/bin/env python3
"""What tracing costs: for each seed, one set-up serves an LM cell's window
twice, once with the profiler off and once with it on around the whole
window, started before the window opens and stopped after it closes (the
order alternates from seed to seed); the engine drains between windows.

    python3 bench/tools/trace_cost.py --workload ds7b-chat --seconds 50 \\
        --seeds 1 2 3

Prints one JSON line per window: the seed, whether it was traced, and its
end-to-end numbers. The trace is written under ``.bench_trace/`` in the
checkout and removed.
"""
import argparse
import gc
import json
import shutil
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
    args = ap.parse_args()
    bench, cell, cfg, traffic = run.load_cell(ROOT, args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    devs, peaks = run.device_check(jax, cell["chips"], ("tpu",),
                                   run.BENCH / "peaks.json")
    from bench.drivers import lm
    trace_dir = str(ROOT / ".bench_trace" / "trace_cost")
    for i, seed in enumerate(args.seeds):
        ctx = run.Ctx(ROOT, cell, cfg, traffic, seed, args.seconds,
                      cell["chips"], time.time(), peaks)
        c = lm.Cell(ctx)
        c.build(seed)
        c.warm()
        for traced in ((False, True) if i % 2 == 0 else (True, False)):
            if traced:
                jax.profiler.start_trace(trace_dir)
            win = c.window(seed, args.seconds)
            if traced:
                jax.profiler.stop_trace()
                shutil.rmtree(trace_dir, ignore_errors=True)
            out = {"seed": seed, "traced": traced,
                   "compiles": win["compiles"], "ticks": len(win["ticks"]),
                   **lm.end_to_end(win)}
            print(json.dumps(out), flush=True)
            while c.target.has_work:
                c.target.tick()
        c.free()
        del c, win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
