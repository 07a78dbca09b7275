"""Unified serving runtime (paper §IV) — module map:

- ``scheduler.py``  — single request queue + admission layer shared by all
  engines: Ticket lifecycle, pluggable policies (FIFO, earliest-deadline-
  first, size x time batch formation), per-request deadline tracking, and
  completion accounting into Telemetry.
- ``executor.py``   — StageExecutor: compiled-stage cache keyed by
  (stage, shape-bucket) with compile and per-stage call counts and a
  ``serve.dispatch`` profiler span; absorbs the engines' private jit
  caches (T5 bucketing).
- ``telemetry.py``  — shared stats surface: QPS, p50/p95/p99 latency,
  queue depth, SLA-miss fraction, compile counts, per-stage dispatches;
  consumed by launch/serve.py, the examples, and bench/run.py.
- ``engine.py``     — LM engine: continuous slot-batched greedy decode
  with bucketed **batched prefill** (freed slots refill together in one
  bucketed call) and **chunked prefill** for every block pattern on the
  shared scheduler/executor.
- ``state.py``      — SequenceStateManager (PR 5): the per-slot state
  contract behind the LM engine — free/active/prefilling slot
  partitions, decode-side read surface, steal-veto and fault-drain
  rules, and the slot-state kinds (KV rows, local rings, recurrent
  state) that let chunked prefill carry state across chunk boundaries
  for ANY architecture; ``require_chunkable`` is the precise capability
  check that replaced the old all-global-attention gate.
- ``dlrm_engine.py``— DLRM engine: 4-stage ingest→sparse→dense→post
  instance of the N-stage pipeline (core/pipeline.py) with the T6
  transfer path as stage 0.
- ``router.py``     — ReplicaRouter: front-end balancer over N engine
  replicas (the paper's six-cards-behind-one-host deployment) routing by
  queue depth + deadline slack, with fleet-level telemetry aggregation
  (``Telemetry.merged``), cross-replica work stealing (``steal=True``:
  idle replicas pull pending fresh tickets from backlogged siblings
  under the ``Scheduler.steal_pending``/``absorb`` re-stamping
  contract), and replica fault drain (``drain_replica``: a dead card's
  accepted work re-homes to the live replicas, never lost). Priority
  classes + admission-control shedding live in the scheduler
  (``priority`` policy, ``max_queue`` / ``service_ms_est``).
- ``fleet_sim.py``  — deterministic discrete-event fleet simulator
  (virtual clock, per-replica service times, seeded arrivals) behind
  the REAL router; drives the work-stealing property suite
  (tests/fleet_sim.py), the fleet-sim scenario tests, and the
  elastic smoke.

The N-stage software-pipeline driver itself lives in
``repro/core/pipeline.py`` (paper T2, Fig. 6 generalized).
"""
from repro.serving.executor import StageExecutor
from repro.serving.router import ReplicaRouter
from repro.serving.scheduler import (NO_SLO, EDFPolicy, FIFOPolicy, Policy,
                                     PriorityAgingPolicy, Scheduler,
                                     SizeTimePolicy, Ticket)
from repro.serving.state import SequenceStateManager, require_chunkable
from repro.serving.telemetry import Telemetry

__all__ = ["StageExecutor", "Scheduler", "Ticket", "Policy", "FIFOPolicy",
           "EDFPolicy", "SizeTimePolicy", "PriorityAgingPolicy",
           "ReplicaRouter", "SequenceStateManager", "require_chunkable",
           "Telemetry", "NO_SLO"]
