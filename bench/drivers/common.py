"""What every driver shares: compile counting, device facts, percentiles,
spans and the traced stretch."""
from __future__ import annotations

import math
import sys
from typing import List, Optional

import jax


class CompileClock:
    """Counts XLA backend compiles while active (JAX's own
    ``/jax/core/compile/backend_compile_duration`` events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


def percentile(vals, p: float) -> float:
    """Linearly interpolated percentile (rank p * (n - 1)); nan if empty."""
    v = sorted(vals)
    if not v:
        return float("nan")
    rank = min(max(p, 0.0), 1.0) * (len(v) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (rank - lo)


def peak_bytes(devices) -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest device, if the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


class TracedStretch:
    """Profiles one stretch of the window into ``trace_dir``, marked by a
    ``bench.window`` span; a no-op when ``trace_dir`` is None."""

    def __init__(self, trace_dir: Optional[str]):
        self.dir = trace_dir
        self.active = False
        self.done = False
        self._span = None

    def start(self):
        if self.dir is None or self.active or self.done:
            return
        jax.profiler.start_trace(self.dir)
        self._span = span("bench.window")
        self._span.__enter__()
        self.active = True

    def stop(self):
        if not self.active:
            return
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active, self.done = False, True


def stream_key(seed: int):
    """A PRNG key for any whole-number seed (wider than 32 bits too)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def sample_indices(rng, n: int, k: int, first: int) -> List[int]:
    """``first`` and then up to k - 1 other indices of range(n), drawn."""
    rest = [i for i in rng.permutation(n) if i != first]
    return [first] + [int(i) for i in rest[:max(k - 1, 0)]]
