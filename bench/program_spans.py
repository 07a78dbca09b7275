"""What the readers of the LM engine's own names find in a reduced trace.

The engine names its compiled stages (``jit_prefill_step``,
``jit_slot_write``, ``jit_chunk_prefill_step``, ``jit_decode_step``: the
``module`` of each device op) and marks its host loop with ``serve.*``
spans (``serve.step`` > ``serve.admit`` / ``serve.prefill`` /
``serve.chunk`` / ``serve.decode`` > ``serve.dispatch`` / ``serve.read``),
on the profiler's clock with the benchmark's ``bench.*`` spans. A program
without these names gives the readers nothing to read: they return None.

Traced ticks are paired with ``bench.step_once`` spans in order, as
``flash_attn_roofline`` pairs them. All times are seconds.
"""
from __future__ import annotations

from collections import defaultdict
from typing import List, Tuple

from bench.trace import union

DECODE = r"^jit_decode_step$"
PREFILL = r"^jit_(prefill_step|slot_write|chunk_prefill_step)$"


def traced_ticks(run) -> list:
    """(``bench.step_once`` span, tick record) of every traced tick."""
    tr = run.trace
    if tr is None or run.win.get("traced_from") is None:
        return []
    spans = sorted(tr.spans_named("bench.step_once"), key=lambda s: s.start)
    return list(zip(spans, run.win["ticks"][run.win["traced_from"]:]))


def decode_only(tick: dict) -> bool:
    return not tick["prefill"] and tick["decode_steps"] > 0


def program_ops(tr, span, pattern: str) -> list:
    """Device ops of the programs matching ``pattern`` inside ``span``."""
    return tr.ops_within(span.start, span.end, pattern, key="module")


def device_s(tr, ops) -> float:
    """Device seconds of ``ops``: each device's union of their intervals,
    summed over devices."""
    by_dev = defaultdict(list)
    for o in ops:
        by_dev[o.device].append(o)
    return sum(tr.device_s(v) for v in by_dev.values())


def host_spans(tr, name: str) -> List[Tuple[float, float]]:
    """The merged intervals of every host event called ``name``, on any
    thread."""
    return union([(e.start, e.end) for events in tr.host.values()
                  for e in events if e.name == name])


def idle_between(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The parts of (lo, hi) that the sorted, merged ``busy`` intervals
    leave uncovered."""
    gaps, t = [], lo
    for a, b in busy:
        if b <= t:
            continue
        if a >= hi:
            break
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def decode_gaps(run) -> List[List[Tuple[float, float]]]:
    """For each pair of consecutive traced ticks that both ran only a
    decode step, and each device that ran ``jit_decode_step`` in both: the
    device's idle intervals between the end of the first tick's decode
    ops and the start of the second's."""
    tr = run.trace
    ticks = traced_ticks(run)
    busy = {d: tr.busy_intervals(d) for d in tr.devices} if ticks else {}
    out = []
    for (s0, t0), (s1, t1) in zip(ticks, ticks[1:]):
        if not (decode_only(t0) and decode_only(t1)):
            continue
        first, second = program_ops(tr, s0, DECODE), \
            program_ops(tr, s1, DECODE)
        for dev in sorted({o.device for o in first}
                          & {o.device for o in second}):
            lo = max(o.end for o in first if o.device == dev)
            hi = min(o.start for o in second if o.device == dev)
            out.append(idle_between(busy[dev], lo, hi))
    return out


def overlap(intervals, others) -> float:
    """Seconds of ``intervals`` that ``others`` (merged) cover."""
    return sum(max(0.0, min(b, y) - max(a, x))
               for a, b in intervals for x, y in others
               if x < b and y > a)
