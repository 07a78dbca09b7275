#!/usr/bin/env python3
"""Run one traced cell and describe its trace: planes, lines, the busiest
event names and their stats, and whether host spans and device events share
one clock. A look at a real trace before trusting its reduction.

    python3 bench/tools/probe.py --workload <cell> --seed <n> --seconds <s>
"""
import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, trace  # noqa: E402


def describe(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace.find_xplane(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: "
              f"{[(l.name, sum(1 for _ in l.events)) for l in lines][:12]}")
        if not plane.name.startswith("/device:"):
            continue
        for l in lines:
            names = Counter(e.name for e in l.events)
            print(f"  line {l.name!r} top: {names.most_common(25)}")
            for e in list(l.events)[:3]:
                print(f"    e.g. {e.name!r} stats {dict(e.stats)}")
            odd = sorted({e.name for e in l.events
                          if any(k in e.name.lower() for k in
                                 ("flash", "custom", "sparse", "mosaic"))})
            print(f"  line {l.name!r} kernels/programs: {odd[:20]}")
    red = trace.reduce(path)
    s = sorted(red.spans_named("bench.step_once"), key=lambda s: s.start)[:3]
    for sp in s:
        inside = [o for o in red.ops if sp.start <= o.start < sp.end]
        print(f"span {sp.start:.6f}-{sp.end:.6f}: {len(inside)} ops inside, "
              f"first {inside[0].start if inside else None}")
    print(f"ops span {red.ops[0].start:.6f}-{red.ops[-1].end:.6f}; window "
          f"{red.window}")
    print("module time:", sorted(red.time_by("module").items(),
                                 key=lambda kv: -kv[1])[:10])


if __name__ == "__main__":
    argv = sys.argv[1:] + ["--trace", "1"]
    code = run.main(argv, keep_trace=True)
    name = argv[argv.index("--workload") + 1]
    describe(str(ROOT / ".bench_trace" / name))
    sys.exit(code)
