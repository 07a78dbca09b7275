"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

The run marks the traced stretch with a host span named ``bench.window`` and
wraps each call into the program in spans named ``bench.<call>``
(``jax.profiler.TraceAnnotation``). From the trace this module takes:

- device operations: on a TPU, the events of the ``XLA Ops`` line of each
  ``/device:...`` plane, with their program from the ``XLA Modules`` line; on
  the CPU backend (tests), the host events that carry an ``hlo_op`` stat;
- busy time per device: the union of its operation intervals inside the
  window, and the idle gaps between them;
- for each idle gap, what the host was doing: the innermost ``bench.`` span
  that covers the gap's midpoint, and the innermost host event inside it.

All times are seconds.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass
class Op:
    start: float
    end: float
    name: str
    module: str
    device: str


@dataclass
class Span:
    start: float
    end: float
    name: str
    thread: str


@dataclass
class Reduced:
    window: Tuple[float, float]
    ops: List[Op]
    spans: List[Span]                      # bench.* host spans
    host: Dict[str, List[Span]]            # every host event, by thread
    devices: List[str] = field(default_factory=list)
    _starts: Dict[str, List[float]] = field(default_factory=dict,
                                            repr=False)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, device: str) -> List[Tuple[float, float]]:
        return union([(o.start, o.end) for o in self.ops
                      if o.device == device], self.window)

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        if not self.devices:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in self.devices) / len(self.devices)

    def time_by(self, key: str = "name", pattern: Optional[str] = None,
                within: Optional[Tuple[float, float]] = None
                ) -> Dict[str, float]:
        """Device seconds by op name (``key="name"``) or program
        (``key="module"``), optionally filtered by a regex on that key and
        clipped to an interval."""
        lo, hi = within or self.window
        out: Dict[str, float] = defaultdict(float)
        rx = re.compile(pattern) if pattern else None
        for o in self.ops:
            k = getattr(o, key)
            if rx is not None and not rx.search(k):
                continue
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                out[k] += d
        return dict(out)

    def ops_within(self, lo: float, hi: float, pattern: str,
                   key: str = "name") -> List[Op]:
        rx = re.compile(pattern)
        return [o for o in self.ops if lo <= o.start and o.end <= hi
                and rx.search(getattr(o, key))]

    def device_s(self, ops: List[Op]) -> float:
        """Seconds in which any of ``ops`` ran: the union of their
        intervals, so an op nested in another (a loop's body inside the
        loop) counts once."""
        return sum(b - a for a, b in union([(o.start, o.end) for o in ops]))

    def spans_named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def idle_gaps(self) -> List[Tuple[str, float, float]]:
        """(label, start, seconds) of every gap in the first device's busy
        union inside the window, longest first."""
        if not self.devices:
            return []
        lo, hi = self.window
        gaps, t = [], lo
        for a, b in self.busy_intervals(self.devices[0]):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        out = [(self.label(0.5 * (a + b)), a, b - a) for a, b in gaps]
        return sorted(out, key=lambda g: -g[2])

    def label(self, t: float) -> str:
        """What the host was doing at ``t``: the innermost bench span and
        the innermost host event inside it on the same thread."""
        covering = [s for s in self.spans if s.start <= t < s.end
                    and s.name != WINDOW_SPAN]
        if not covering:
            return "outside bench spans"
        span = min(covering, key=lambda s: s.end - s.start)
        e = self._innermost(span, t)
        return span.name if e is None else f"{span.name} > {e.name}"[:96]

    def _innermost(self, span: Span, t: float) -> Optional[Span]:
        """The latest-starting host event on the span's thread that covers
        ``t`` and lies inside the span (events on one thread nest, so that
        is the innermost one)."""
        events = self.host.get(span.thread, [])
        starts = self._starts.setdefault(span.thread,
                                         [e.start for e in events])
        i = bisect.bisect_right(starts, t) - 1
        for _ in range(5000):
            if i < 0 or events[i].start < span.start:
                return None
            e = events[i]
            if e.end > t and not e.name.startswith(SPAN_PREFIX):
                return e
            i -= 1
        return None

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (ops that hold no
        other op, named ``program:op``), and the idle time by what the
        host was doing."""
        by_op: Dict[str, float] = defaultdict(float)
        lo, hi = self.window
        for o in leaves(self.ops):
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                by_op[f"{o.module}:{o.name}"] += d
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])
        by_label: Dict[str, float] = defaultdict(float)
        for lab, _, sec in self.idle_gaps():
            by_label[lab] += sec
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def union(intervals, window=None) -> List[Tuple[float, float]]:
    """Sorted, merged intervals, clipped to ``window``."""
    lo, hi = window if window else (-float("inf"), float("inf"))
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def leaves(ops: List[Op]) -> List[Op]:
    """The ops (sorted by start) that contain no other op of their device:
    a loop or call whose body's ops run inside its own interval is left
    out, so summed leaf times count each instant once."""
    out = []
    by_dev: Dict[str, List[Op]] = defaultdict(list)
    for o in ops:
        by_dev[o.device].append(o)
    for dev_ops in by_dev.values():
        dev_ops = sorted(dev_ops, key=lambda o: (o.start, -o.end))
        for i, o in enumerate(dev_ops):
            nxt = dev_ops[i + 1] if i + 1 < len(dev_ops) else None
            if nxt is None or not (nxt.start < o.end and nxt.end <= o.end):
                out.append(o)
    return sorted(out, key=lambda o: o.start)


def _op_name(event_name: str) -> str:
    """``%fusion.119 = s32[4]{...} fusion(...)`` -> ``fusion.119``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:           # events without readable stats
        return {}


def reduce(path: str) -> Reduced:
    """Read one ``.xplane.pb`` (or the newest one under a trace directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    host: Dict[str, List[Span]] = defaultdict(list)
    device_planes = [p for p in data.planes if p.name.startswith("/device:")]
    for plane in device_planes:
        lines = {l.name: l for l in plane.lines}
        mods = sorted((e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9, e.name)
                      for e in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        mod_starts = [m[0] for m in mods]
        if "XLA Ops" not in lines:
            continue
        for e in lines["XLA Ops"].events:
            a = e.start_ns * 1e-9
            b = a + e.duration_ns * 1e-9
            j = bisect.bisect_right(mod_starts, a) - 1
            mod = mods[j][2] if j >= 0 and a < mods[j][1] else ""
            ops.append(Op(a, b, _op_name(e.name), _module_name(mod),
                          plane.name))
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                a = e.start_ns * 1e-9
                b = a + e.duration_ns * 1e-9
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(a, b, e.name, line.name))
                if not device_planes:
                    st = _stats(e)
                    if "hlo_op" in st:
                        ops.append(Op(a, b, str(st["hlo_op"]),
                                      str(st.get("hlo_module", "")), "cpu"))
                        continue
                host[line.name].append(Span(a, b, e.name, line.name))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[0].end)
    elif spans:
        window = (min(s.start for s in spans), max(s.end for s in spans))
    else:
        raise ValueError(f"{path}: no bench spans in the trace")
    devices = sorted({o.device for o in ops})
    for events in host.values():
        events.sort(key=lambda e: e.start)
    return Reduced(window, sorted(ops, key=lambda o: o.start), spans,
                   dict(host), devices)


def _module_name(event_name: str) -> str:
    """``jit_sparse_fn(12)`` -> ``jit_sparse_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)
