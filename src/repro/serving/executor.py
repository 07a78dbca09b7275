"""Shared compiled-stage executor — the runtime's "multiple compiled
networks, switch at runtime" machinery (paper T5 / §VI-A) factored out of
the engines.

A StageExecutor is a cache of compiled callables keyed by
``(stage_name, shape_key)`` — e.g. ``("prefill", (bucket, batch))`` or
``("sparse", ())`` — with compile and per-stage call counts.
It absorbs what the seed engines hand-rolled privately:
``InferenceEngine._prefill_cache`` / ``_get_prefill`` and the jitted
stages built in ``DLRMEngine.__post_init__``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Hashable, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.serving.telemetry import Telemetry

StageKey = Tuple[str, Hashable]


class StageExecutor:
    """Compiled-stage cache + dispatch wrapper.

    ``get`` returns (building if needed) the executable for a stage/shape;
    ``dispatch`` additionally counts the call and wraps it in a
    ``serve.dispatch`` profiler span. With JAX async dispatch the call
    returns once the work is enqueued, so the span covers the enqueue; the
    device time is in the same trace, under the stage's program name.
    """

    def __init__(self, telemetry: Optional[Telemetry] = None):
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._cache: Dict[StageKey, Callable] = {}

    def get(self, stage: str, key: Hashable,
            build_fn: Callable[[], Callable]) -> Callable:
        """Executable for (stage, key), building via build_fn on miss."""
        k = (stage, key)
        fn = self._cache.get(k)
        if fn is None:
            fn = self._cache[k] = build_fn()
            self.telemetry.record_compile(stage)
        return fn

    def dispatch(self, stage: str, key: Hashable,
                 build_fn: Callable[[], Callable], *args, **kw) -> Any:
        """get() + call, counting the call per stage."""
        fn = self.get(stage, key, build_fn)
        with TraceAnnotation("serve.dispatch"):
            out = fn(*args, **kw)
        self.telemetry.record_dispatch(stage)
        return out

    def compiles_for(self, stage: str) -> int:
        return self.telemetry.compiles.get(stage, 0)

    def cached_keys(self, stage: Optional[str] = None):
        return [k for k in self._cache
                if stage is None or k[0] == stage]

    def __len__(self) -> int:
        return len(self._cache)
