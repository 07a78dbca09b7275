#!/usr/bin/env python3
"""Run one traced cell and split its trace by the engine's own names: the
device's idle time by the innermost ``serve.*`` span the host was in
(``serve.read``, the self time of ``serve.decode``, ``serve.admit``, ...;
inside a ``bench.*`` span but in no ``serve.*`` span is the benchmark's
loop, outside the engine), and the device time by program (each
program's union of op intervals, so an op nested in another counts once).

    python3 bench/tools/idle_split.py --workload <cell> --seed <n> \\
        --seconds <s>

Prints the run's own result line, then one JSON line with the split.
"""
import bisect
import json
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, trace  # noqa: E402
from bench.program_spans import device_s, idle_between  # noqa: E402


def split(red) -> dict:
    lo, hi = red.window
    idle = idle_between(red.busy_intervals(red.devices[0]), lo, hi)
    spans = sorted((e for events in red.host.values() for e in events
                    if e.name.startswith("serve.")),
                   key=lambda e: e.start)
    bench = sorted((s for s in red.spans if s.name != trace.WINDOW_SPAN),
                   key=lambda s: s.start)
    starts = [s.start for s in spans]
    bounds = sorted({t for s in spans for t in (s.start, s.end)})
    by = defaultdict(float)
    for a, b in idle:
        cuts = [a] + bounds[bisect.bisect_right(bounds, a):
                            bisect.bisect_left(bounds, b)] + [b]
        for x, y in zip(cuts, cuts[1:]):
            by[label(spans, starts, bench, 0.5 * (x + y))] += y - x
    programs = defaultdict(list)
    for o in red.ops:
        programs[o.module].append(o)
    return {"idle_s": sum(b - a for a, b in idle),
            "idle_by_span": dict(sorted(by.items(), key=lambda kv: -kv[1])),
            "device_by_program": dict(sorted(
                ((m, device_s(red, ops)) for m, ops in programs.items()),
                key=lambda kv: -kv[1]))}


def label(spans, starts, bench, t) -> str:
    """The innermost serve span at ``t`` (the latest-starting one that
    covers it: spans on one thread nest), else the innermost bench span."""
    for j in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[j].end > t:
            return spans[j].name
    covering = [s for s in bench if s.start <= t < s.end]
    if covering:
        return min(covering, key=lambda s: s.end - s.start).name \
            + " (no serve span)"
    return "outside bench spans"


if __name__ == "__main__":
    argv = sys.argv[1:] + ["--trace", "1"]
    code = run.main(argv, keep_trace=True)
    name = argv[argv.index("--workload") + 1]
    print(json.dumps(split(trace.reduce(str(ROOT / ".bench_trace" / name)))),
          flush=True)
    sys.exit(code)
