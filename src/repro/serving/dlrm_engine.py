"""DLRM serving engine on the unified runtime — the paper's Fig. 6
pipeline end-to-end as a 4-stage instance of the shared N-stage driver:

  stage 0 ingest: host feature ingestion (partial transfers + command
                  batching, T6 — core/transfer.py)
  stage 1 sparse: SLS over partitioned tables (T1), model-parallel
  stage 2 dense:  bottom MLP + interaction + top MLP, data-parallel
  stage 3 post:   output normalization (float32 logits)

with request N's dense overlapping request N+1's sparse (T2) and request
N+2's host ingest — the generalization of the paper's two-stage overlap.
Compiled stages live in the shared StageExecutor; admission/latency/SLA
accounting flows through the shared Scheduler + Telemetry.

The ingest ships each request's ids as one ``(B, W)`` staging buffer,
item-major (each item's bags back to back in table order, then zeros;
``W`` the width bucket of the longest item), and the sparse stage
gathers straight from it. The first request of each batch size compiles
the sparse stage for every width bucket, so later requests compile nothing
whatever their bag lengths. Profiler spans use the LM engine's names:
``serve.step`` around ``step_once``, ``serve.admit`` around admission,
``serve.ingest`` around each request's host ingest, and the executor's
``serve.dispatch``; the compiled stages are the programs ``jit_sparse_fn``
and ``jit_dense_fn``.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.dlrm_paper import DLRMConfig
from repro.core.partitioner import TableAssignment
from repro.core.pipeline import Pipeline, PipelineStats
from repro.core.transfer import (SparseBatch, TransferStats, naive_transfer,
                                 staged_transfer, width_buckets)
from repro.models import dlrm as dlrm_mod
from repro.serving.executor import StageExecutor
from repro.serving.scheduler import Scheduler
from repro.serving.telemetry import Telemetry


@dataclass
class DLRMEngine:
    cfg: DLRMConfig
    assignment: TableAssignment
    params: Any
    partial_transfers: bool = True
    policy: str = "fifo"
    slo_ms: Optional[float] = None
    max_queue: Optional[int] = None
    service_ms_est: Optional[float | str] = None   # number or "auto"
    step_group: int = 4       # max batches admitted per step_once (router
                              # interleaving granularity; >=2 keeps the T2
                              # stage overlap alive within a step)
    transfer_stats: TransferStats = field(default_factory=TransferStats)

    def __post_init__(self):
        cfg, asn = self.cfg, self.assignment
        slab_q = (self.params or {}).get("slab_q")
        if isinstance(slab_q, dict) and "sb" not in slab_q:
            self.params = {**self.params,
                           "slab_q": dlrm_mod.pack_scale_bias(slab_q)}
        self.telemetry = Telemetry()
        self.stats = self.telemetry
        self.executor = StageExecutor(self.telemetry)
        self.scheduler = Scheduler(self.policy, telemetry=self.telemetry,
                                   default_slo_ms=self.slo_ms,
                                   max_queue=self.max_queue,
                                   service_ms_est=self.service_ms_est)
        self._collect_transfer_stats = True
        self._sparse_warm = set()           # batch sizes whose widths are built

        def build_sparse():
            @jax.jit
            def sparse_fn(params, indices, lengths):
                return dlrm_mod.sls_forward(params, cfg, asn, indices,
                                            lengths)
            return sparse_fn
        self._build_sparse = build_sparse

        def build_dense():
            @jax.jit
            def dense_fn(params, pooled, dense_x):
                return dlrm_mod.dense_forward(params, cfg, dense_x, pooled)
            return dense_fn

        def build_post():
            return jax.jit(lambda logits: logits.astype(jnp.float32))

        ex = self.executor
        self._pipeline = Pipeline([
            ("ingest", lambda x, req: self.ingest(req)),
            ("sparse", lambda x, req: {
                "pooled": ex.dispatch("sparse", (), build_sparse,
                                      self.params, *x["sls"]),
                "dense": x["dense"]}),
            ("dense", lambda x, req: ex.dispatch(
                "dense", (), build_dense, self.params, x["pooled"],
                x["dense"])),
            ("post", lambda x, req: ex.dispatch("post", (), build_post, x)),
        ])

    def ingest(self, batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """Host->device input path with the paper's T6 optimizations."""
        with TraceAnnotation("serve.ingest"):
            sb = SparseBatch(batch["indices"], batch["lengths"])
            stats = self.transfer_stats if self._collect_transfer_stats \
                else None
            if self.partial_transfers:
                B, T, L = sb.indices.shape
                if B not in self._sparse_warm:
                    self._warm_sparse(B, T, L)
                    self._sparse_warm.add(B)
                idx_dev, len_dev = staged_transfer(sb, stats)
            else:
                idx_dev, len_dev = naive_transfer(sb, stats)
            return {"sls": (idx_dev, len_dev),
                    "dense": jnp.asarray(batch["dense"])}

    def _warm_sparse(self, B: int, T: int, L: int) -> None:
        """Compile the sparse stage for every staged width of batch size
        ``B``: lowered here, so under this thread's JAX settings, then the
        XLA compiles in parallel threads. Later calls find them."""
        fn = self.executor.get("sparse", (), self._build_sparse)
        lens = jax.ShapeDtypeStruct((B, T), jnp.int32)
        lowered = [fn.lower(self.params,
                            jax.ShapeDtypeStruct((B, w), jnp.int32), lens)
                   for w in width_buckets(T * L)]
        with ThreadPoolExecutor(len(lowered)) as pool:
            list(pool.map(lambda lo: lo.compile(), lowered))

    # ---- replica protocol (ReplicaRouter) --------------------------------
    def submit(self, batch: Dict[str, np.ndarray], *,
               slo_ms: Optional[float] = None,
               priority: Optional[int] = None):
        """Enqueue one raw host batch; returns the scheduler ticket
        (``shed=True`` if admission control rejected it)."""
        return self.scheduler.submit(batch, size=len(batch["lengths"]),
                                     slo_ms=slo_ms,
                                     priority=priority or 0)

    @property
    def inflight(self) -> int:
        return 0          # the pipeline pass in step_once is synchronous

    @property
    def free_slots(self) -> int:
        """Steal admission cap (router hook): the pipeline pass is
        synchronous, so capacity is the per-step admission group."""
        return self.step_group

    @property
    def has_work(self) -> bool:
        return self.scheduler.depth > 0

    def steal_eligible(self, t) -> bool:
        """Steal veto (router hook): a pending DLRM batch holds no device
        state yet, so everything fresh may move; continuations don't
        exist on this engine but the guard keeps the contract uniform."""
        return not t.continuation

    def drain_tickets(self):
        """Fault-drain hook: the whole pending queue (nothing is ever in
        flight between steps), reset to fresh for re-homing."""
        out = self.scheduler.steal_pending(None, include_continuations=True)
        for t in out:
            t.reset_fresh()
        return out

    def step_once(self) -> List[Any]:
        """Admit one policy-formed group (at most ``step_group`` batches,
        so a routed fleet actually interleaves replica steps instead of
        serially draining whole queues) and run it through the 4-stage
        pipeline, completing tickets as outputs realize."""
        with TraceAnnotation("serve.step"):
            with TraceAnnotation("serve.admit"):
                group = self.scheduler.admit(min(self.scheduler.depth,
                                                 self.step_group))
            if not group:
                return []
            done = lambda i, _v: self.scheduler.complete(group[i])
            outs, _ = self._pipeline.run([t.payload for t in group],
                                         on_result=done)
            return outs

    def serve(self, batches: Sequence[Dict[str, np.ndarray]],
              pipelined: bool = True, warm: bool = False,
              measure: bool = False) -> Tuple[List[Any], PipelineStats]:
        """Run raw host batches through admission + the 4-stage pipeline.

        ``warm=True`` marks compile/warm-up traffic: it is excluded from
        transfer stats and from latency/QPS telemetry.
        """
        if warm:
            with self._suppress_traffic_stats():
                if pipelined:
                    return self._pipeline.run(batches, measure=measure)
                return self._pipeline.run_sequential(batches)
        tickets = [self.scheduler.submit(b, size=len(b["lengths"]))
                   for b in batches]
        # drain the queue group by group: a batch-forming policy (sizetime)
        # returns one size-coherent group per admit() call
        admitted = []
        while self.scheduler.depth:
            got = self.scheduler.admit(len(tickets))
            if not got:
                break
            admitted.append(got)
        outs, stats = [], PipelineStats()
        t0 = time.perf_counter()
        for group in admitted:
            reqs = [t.payload for t in group]
            # per-ticket completion as each output is realized, so tail
            # latency reflects position in the pipeline, not one lump
            # timestamp for the whole pass
            done = lambda i, _v: self.scheduler.complete(group[i])
            if pipelined:
                o, s = self._pipeline.run(reqs, on_result=done)
            else:
                o, s = self._pipeline.run_sequential(reqs, on_result=done)
            outs.extend(o)
            stats.num_requests += s.num_requests
            stats.wall_time_s += s.wall_time_s
        self.telemetry.record_serving_window(time.perf_counter() - t0)
        if measure:
            # stage re-execution for timing must not double-count the
            # T6 transfer stats or stage call counts collected by the
            # production pass above
            with self._suppress_traffic_stats():
                stats.stage_time_s = self._pipeline.measure_stages(
                    [t.payload for g in admitted for t in g])
        return outs, stats

    @contextmanager
    def _suppress_traffic_stats(self):
        """Exclude non-production traffic (warm-up, measurement re-runs)
        from transfer stats and per-stage call counts."""
        self._collect_transfer_stats = False
        calls = dict(self.telemetry.stage_calls)
        try:
            yield
        finally:
            self._collect_transfer_stats = True
            self.telemetry.stage_calls = calls


def make_replicas(cfg: DLRMConfig, assignment: TableAssignment, params: Any,
                  n: int, **engine_kw) -> List["DLRMEngine"]:
    """N DLRM engine replicas sharing one set of (quantized) tables and
    dense weights — the paper's multiple-cards-per-host deployment.
    Front with ``ReplicaRouter``."""
    if isinstance(params.get("slab_q"), dict):
        params = {**params,
                  "slab_q": dlrm_mod.pack_scale_bias(params["slab_q"])}
    return [DLRMEngine(cfg, assignment, params, **engine_kw)
            for _ in range(n)]
