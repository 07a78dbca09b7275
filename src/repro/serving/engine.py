"""LM serving engine on the unified runtime — the paper's §IV custom
service binary, TPU-native:

- shared Scheduler (scheduler.py) for the request queue + admission (the
  Glow runtime's multi-request queue/overlap, §IV-C): slots decode at
  independent positions, freed slots are refilled immediately under a
  pluggable policy (FIFO / EDF / size x time batch formation)
- shared StageExecutor (executor.py) for every compiled stage: bucketed
  prefill executables (T5), the decode step, and the slot-scatter writer
- **batched prefill**: freed slots are refilled together — admitted
  requests are grouped by prefill bucket and each group prefills in ONE
  bucketed call instead of per-request batch-1 dispatches
- **chunked prefill** (``prefill_chunk=N``): long prompts split into
  N-token chunks that interleave with decode steps — the unified tick
  runs at most ONE chunk group, then one decode step, so a 128-token
  prefill can no longer stall every decode slot behind it (head-of-line
  blocking, the tail-TTFT killer in the latency-bounded batching
  analysis of Park et al. 2018). Mid-prefill requests re-enter the
  queue as *continuation tickets* (same tid/enqueue/priority/deadline)
  and every block kind carries its per-slot state across the chunk
  boundary — global K/V scatters at the chunk's offset, local rings
  write at ring offsets, SSM / RG-LRU blocks carry the entering
  recurrent state + conv tail (PR 5) — so chunked prefill is
  token-identical to monolithic prefill for EVERY ``block_pattern``
  (the old all-global gate is gone; only cross-attention
  encoder-decoder stacks stay unchunkable, see
  ``repro.serving.state.require_chunkable``)
- per-slot sequence state behind the ``SequenceStateManager``
  (serving/state.py): one free / active / prefilling partition over the
  statically-shaped cache, with the steal-veto and fault-drain slot
  rules — the bookkeeping this engine used to carry inline
- greedy decode loop with async dispatch, per-request deadline/SLA and
  time-to-first-token tracking through the shared Telemetry

The DLRM pipelined engine (T2) lives in dlrm_engine.py on the same stack.
"""
from __future__ import annotations

import hashlib
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.bucketing import pick_bucket
from repro.core.transfer import (TransferStats, snapshot_device_get,
                                 snapshot_device_put)
from repro.models import model as model_mod
from repro.serving.executor import StageExecutor
from repro.serving.perf_model import PerfModel
from repro.serving.scheduler import Scheduler, SizeTimePolicy, Ticket
from repro.serving.state import (SequenceSnapshot, SequenceStateManager,
                                 require_chunkable)
from repro.serving.telemetry import Telemetry


@dataclass
class Request:
    rid: int
    tokens: np.ndarray                 # prompt token ids (L,)
    max_new_tokens: int = 16
    slo_ms: Optional[float] = None     # per-request latency SLA
    priority: int = 0                  # 0 = most important (priority policy)
    output: List[int] = field(default_factory=list)
    enqueue_t: float = 0.0
    finish_t: float = 0.0
    done: bool = False
    shed: bool = False                 # rejected by admission control
    prefill_pos: int = 0               # prompt tokens already prefilled

    @property
    def latency_ms(self) -> float:
        return (self.finish_t - self.enqueue_t) * 1e3


def _cache_batch_axes(cfg: ModelConfig, max_len: int):
    """Per-leaf batch-axis index of the KV-cache pytree, found by abstract
    evaluation at two batch sizes (no device allocation). ``-1`` marks a
    leaf without a batch axis (a None leaf would be eaten by jax.tree.map
    as an empty subtree)."""
    s2 = jax.eval_shape(lambda: model_mod.init_caches(cfg, 2, max_len))
    s3 = jax.eval_shape(lambda: model_mod.init_caches(cfg, 3, max_len))

    def axis(a, b):
        diff = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
        return diff[0] if diff else -1

    return jax.tree.map(axis, s2, s3)


def _cache_seq_axes(cfg: ModelConfig, batch_slots: int, max_len: int):
    """Per-leaf sequence-axis index of the KV-cache pytree, found like
    ``_cache_batch_axes`` by abstract evaluation at two ``max_len``
    values. ``-1`` marks a leaf whose extents don't scale with the
    sequence length — ring buffers (fixed window), recurrent state, conv
    tails — which the snapshot contract moves whole: their state is not
    addressable by prefix position. Leaves WITH a sequence axis (global
    K/V rows and their int8 scales) snapshot only the written prefix
    ``[0, length)`` — the partial-transfer saving. A window that is
    clamped to ``max_len`` shows up as a sequence axis, which is still
    exact: a full-length ring is positionally degenerate (ring offset ==
    position for every written token)."""
    sA = jax.eval_shape(
        lambda: model_mod.init_caches(cfg, batch_slots, max_len))
    sB = jax.eval_shape(
        lambda: model_mod.init_caches(cfg, batch_slots, max_len + 8))

    def axis(a, b):
        diff = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
        return diff[0] if diff else -1

    return jax.tree.map(axis, sA, sB)


class InferenceEngine:
    """Greedy-decoding LM server: bucketed batched prefill + continuous
    slot-batched decode (per-slot positions) on the shared runtime."""

    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_len: int = 256,
                 prefill_buckets: Sequence[int] = (32, 64, 128),
                 policy: str = "fifo", slo_ms: Optional[float] = None,
                 max_prefill_batch: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 service_ms_est: Optional[float | str] = None,
                 service_ms_fallback: Optional[float] = None,
                 prefill_chunk: Optional[int | str] = None,
                 perf_model: Optional[PerfModel] = None,
                 precision: str = "fp32",
                 quantized_params=None,
                 quant_budget: float = 0.05,
                 prefix_cache: Optional[int] = None,
                 page_host: bool = False,
                 page_victim: str = "lru",
                 migrate_min_tokens: Optional[int] = None,
                 device: Optional[jax.Device] = None):
        if page_victim not in ("lru", "remaining"):
            raise ValueError(f"page_victim must be 'lru' or 'remaining', "
                             f"got {page_victim!r}")
        if precision not in ("fp32", "w8a8"):
            raise ValueError(f"precision must be 'fp32' or 'w8a8', "
                             f"got {precision!r}")
        self.cfg = cfg
        self.params = params               # fp32 reference weights
        self.precision = precision
        self.quant = None                  # QuantizedParams build record
        if precision == "w8a8":
            # §V build step: every dense projection goes per-channel int8
            # (over-budget sites stay fp32 via the workflow's skip-list);
            # make_replicas builds ONCE and shares across replicas
            if quantized_params is None:
                from repro.models.quantize import build_quantized_params
                quantized_params = build_quantized_params(
                    cfg, params, budget=quant_budget)
            self.quant = quantized_params
            self.run_params = quantized_params.params
        else:
            self.run_params = params
        # pinned replica: weights and caches live on ``device`` (a copy per
        # replica); None keeps JAX's default placement
        if device is not None:
            self.run_params = jax.device_put(self.run_params, device)
        self.max_len = max_len
        self.batch_slots = batch_slots
        self.buckets = tuple(b for b in prefill_buckets if b <= max_len)
        # max_prefill_batch=1 reproduces the seed's per-request prefill
        # (kept for A/B tests); default admits up to all free slots at once
        self.max_prefill_batch = max_prefill_batch or batch_slots

        # analytic perf model (PR 9), sized from the fp32 weights: prices
        # the auto prefill chunk, the estimator's cold-start priors, and
        # the router's per-precision scale-up seed
        self.perf_model = (perf_model if perf_model is not None
                           else PerfModel.for_params(params, device=device))
        if prefill_chunk == "auto":
            # self-tuning knob: the chunk at the model's per-bucket
            # efficiency knee instead of a hand-set literal (chunked
            # prefill is token-identical for ANY chunk, so this only
            # moves the latency/efficiency trade, never the outputs)
            prefill_chunk = self.perf_model.suggest_prefill_chunk(
                self.buckets)
        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            # precise capability check (PR 5): every state-carrying block
            # kind chunks — global KV, local rings, SSM / RG-LRU state —
            # so this raises only for kinds with no per-slot chunk
            # contract (cross-attention encoder-decoder stacks)
            require_chunkable(cfg)
            # chunk ladder: the existing bucket ladder truncated at the
            # chunk size — chunk executables replace the full-length
            # prefill buckets, which is where the compile-count win
            # comes from (no (128,P)/(256,P) prefill programs at all)
            self.chunk_buckets = tuple(sorted(
                {b for b in self.buckets if b <= prefill_chunk}
                | {prefill_chunk}))

        self.telemetry = Telemetry()
        self.stats = self.telemetry          # legacy accessor name
        self.executor = StageExecutor(self.telemetry)
        if policy == "sizetime":
            # batch formation must group on the engine's actual prefill
            # buckets, or "coherent" groups still split into multiple
            # compiled dispatches
            policy = SizeTimePolicy(self.chunk_buckets if prefill_chunk
                                    else self.buckets)
        self.scheduler = Scheduler(policy, telemetry=self.telemetry,
                                   default_slo_ms=slo_ms,
                                   max_queue=max_queue,
                                   service_ms_est=service_ms_est,
                                   service_ms_fallback=service_ms_fallback,
                                   perf_model=self.perf_model)

        with jax.default_device(device):     # None: JAX's default
            self.caches = jax.device_put(
                model_mod.init_caches(cfg, batch_slots, max_len), device)
        self._batch_axes = _cache_batch_axes(cfg, max_len)
        self._seq_axes = _cache_seq_axes(cfg, batch_slots, max_len)
        # per-slot sequence state: the free/active/prefilling partition,
        # per-slot decode positions, and the steal/drain slot rules all
        # live in the manager (serving/state.py)
        self.states = SequenceStateManager(batch_slots, cfg)

        # movable sequence state (PR 8) — one snapshot contract, three
        # consumers: prefix cache, host-RAM paging, mid-prefill migration
        self.transfer_stats = TransferStats()    # staged snapshot traffic
        if prefix_cache is not None and prefill_chunk is None:
            raise ValueError("prefix_cache requires prefill_chunk: cache "
                             "keys are prompt prefixes at chunk granularity")
        self.prefix_cache = prefix_cache         # max cached prefixes (LRU)
        self._prefix_cache: "OrderedDict[Tuple[int, str], SequenceSnapshot]" \
            = OrderedDict()
        # submit-time hits waiting for their first chunk admission:
        # id(ticket) -> snapshot to restore into the acquired slot
        self._pending_restore: Dict[int, SequenceSnapshot] = {}
        # fleet-shared prefix tier (PR 10): the router installs its
        # FleetPrefixIndex here via attach_prefix_index — None means the
        # cache stays purely per-engine (the pre-fleet behaviour)
        self._prefix_index = None
        self._replica_id: Optional[int] = None
        self.page_host = page_host
        self.page_victim = page_victim
        # LRU-by-last-decode bookkeeping: slot -> decode-step stamp of the
        # slot's most recent emitted token (activation counts as a touch)
        self._last_decode: Dict[int, int] = {}
        # paged-out sessions in fault-back (FIFO) order:
        # id(ticket) -> (ticket, snapshot)
        self._paged: "OrderedDict[int, Tuple[Ticket, SequenceSnapshot]]" \
            = OrderedDict()
        # migration cost floor: ship a mid-prefill snapshot only once at
        # least this many tokens of chunk work would otherwise be redone
        # (default: one full chunk — below that a restart costs no more
        # than the snapshot round-trip)
        self.migrate_min_tokens = (migrate_min_tokens
                                   if migrate_min_tokens is not None
                                   else (prefill_chunk or 0))

    # slot-state views (the manager owns them; tests and the router's
    # engine hooks read these)
    @property
    def free(self) -> List[int]:
        return self.states.free

    @property
    def active(self) -> Dict[int, Ticket]:
        return self.states.active

    @property
    def prefilling(self) -> Dict[int, int]:
        return self.states.prefilling

    @property
    def pos(self) -> np.ndarray:
        return self.states.pos

    # ---- compiled stages -------------------------------------------------
    def _build_prefill(self, bucket: int):
        cfg, max_len = self.cfg, self.max_len

        def prefill_step(params, tokens, length):
            valid = jnp.arange(bucket)[None, :] < length[:, None]
            caches = model_mod.init_caches(cfg, tokens.shape[0], max_len)
            x, caches, _ = model_mod.forward(
                params, cfg, {"tokens": tokens}, mode="prefill",
                caches=caches, kv_valid=valid)
            last = x[jnp.arange(x.shape[0]), length - 1]
            nxt = model_mod.greedy_next(params, cfg, last)
            return nxt, caches

        return jax.jit(prefill_step)

    def _build_decode(self):
        cfg = self.cfg

        def decode_step(params, caches, tokens, pos_vec, active):
            hidden, caches = model_mod.decode_step(params, cfg, tokens,
                                                   caches, pos_vec,
                                                   active=active)
            nxt = model_mod.greedy_next(params, cfg, hidden)
            return nxt, caches

        # in-place cache update (the engine drops its old reference)
        return jax.jit(decode_step, donate_argnums=(1,))

    def _build_chunk(self, bucket: int):
        """Chunk-prefill executable: ``bucket``-token chunks for the P
        group rows against the live full-batch cache. The chunk K/V
        scatters into the donated cache at per-row offsets (O(chunk)
        in-place update, like a decode write — gathering and
        re-scattering whole cache rows would move the full KV tree every
        tick and erase the interleaving win), and only the P group rows
        compute the chunk forward (the rest of the batch doesn't burn
        flops on parked tokens). Cached under ("chunk_prefill",
        (bucket, P)) with bucket <= prefill_chunk, so the executable
        ladder stops at the chunk size instead of growing one program
        per full prompt-length bucket.

        Padded group rows duplicate slot ``slots[0]`` but carry
        ``write_pos = max_len`` and ``lengths = 0``: their scatter
        indices (positional caches) or batch rows (ring / recurrent
        caches) are out of bounds and drop, so a duplicate can never
        clobber the real row."""
        cfg = self.cfg

        def chunk_prefill_step(params, caches, slots, tokens, start,
                               write_pos, lengths, last_idx):
            x, caches = model_mod.chunk_prefill_step(
                params, cfg, tokens, caches, slots, start, write_pos,
                lengths)
            hidden = x[jnp.arange(x.shape[0]), last_idx]
            nxt = model_mod.greedy_next(params, cfg, hidden)
            return nxt, caches

        return jax.jit(chunk_prefill_step, donate_argnums=(1,))

    def _build_slot_write(self):
        axes = self._batch_axes

        def slot_write(dst_tree, src_tree, slots):
            # src may carry trailing padded rows (fixed prefill batch);
            # only the first len(slots) rows are real
            def upd(dst, src, ax):
                if ax < 0:             # no batch axis: whole-leaf state
                    return src.astype(dst.dtype)
                d = jnp.moveaxis(dst, ax, 0)
                s = jnp.moveaxis(src, ax, 0)[:slots.shape[0]]
                return jnp.moveaxis(d.at[slots].set(s.astype(dst.dtype)),
                                    0, ax)

            return jax.tree.map(upd, dst_tree, src_tree, axes)

        # donate the destination tree: scatter in place, no full copy
        return jax.jit(slot_write, donate_argnums=(0,))

    # ---- movable sequence state: serialize / restore (PR 8) --------------
    def snapshot_slot(self, slot: int, length: int, *,
                      pos: int = 0) -> SequenceSnapshot:
        """Serialize one slot's sequence state to a host-side
        ``SequenceSnapshot``: per cache leaf, the slot's batch row with
        sequence axes sliced to the written prefix ``[0, length)`` and
        non-positional state (rings, recurrent state, conv tails) copied
        whole. One batched device->host transfer ships all leaves (the
        command-batching trick from ``core/transfer.py``, with the
        partial-vs-full byte accounting in ``transfer_stats``)."""
        bax, sax = self._batch_axes, self._seq_axes

        def take(leaf, b, s):
            if b < 0:                  # whole-leaf state: moves verbatim
                return leaf
            row = jnp.take(leaf, slot, axis=b)
            if s >= 0:
                ax = s - (b < s)       # seq axis after the batch axis drops
                row = jax.lax.slice_in_dim(
                    row, 0, min(length, row.shape[ax]), axis=ax)
            return row

        rows = jax.tree.map(take, self.caches, bax, sax)
        full = sum(
            leaf.nbytes // (leaf.shape[b] if b >= 0 else 1)
            for leaf, b in zip(jax.tree.leaves(self.caches),
                               jax.tree.leaves(bax)))
        host = snapshot_device_get(rows, self.transfer_stats,
                                   full_bytes=full)
        partial = sum(np.asarray(x).nbytes for x in jax.tree.leaves(host))
        return SequenceSnapshot(length=length, pos=pos, leaves=host,
                                bytes_partial=partial, bytes_full=full)

    def restore_slot(self, snap: SequenceSnapshot, slot: int) -> None:
        """Restore a snapshot into ANY free slot: sliced sequence axes
        zero-pad back to full rows (positions >= ``snap.length`` are
        never attended before decode or a chunk rewrites them, so the
        padding is unobservable), one batched host->device put stages
        the row tree, and the engine's donated slot-write executable
        scatters it into the target row — the same scatter contract the
        bucketed prefill write uses."""
        bax, sax = self._batch_axes, self._seq_axes

        def expand(row, leaf, b, s):
            if b < 0:                  # whole-leaf state: restore verbatim
                return row
            row = np.asarray(row)
            if s >= 0:
                ax = s - (b < s)
                want = leaf.shape[s]
                if row.shape[ax] < want:
                    pad = [(0, 0)] * row.ndim
                    pad[ax] = (0, want - row.shape[ax])
                    row = np.pad(row, pad)
            return np.expand_dims(row, b)

        src = jax.tree.map(expand, snap.leaves, self.caches, bax, sax)
        dev = snapshot_device_put(src, self.transfer_stats)
        self.caches = self.executor.dispatch(
            "slot_write", 1, self._build_slot_write,
            self.caches, dev, jnp.asarray([slot], jnp.int32))

    # ---- prefix cache (consumer 1) ---------------------------------------
    def _prefix_key(self, tokens: np.ndarray, length: int):
        """Cache key for a prompt prefix: (length, sha1 of the token ids).
        Content-hashed at chunk granularity — two requests sharing a
        system prompt share every chunk-multiple prefix key, whatever
        their suffixes. The cache is per-engine, so config/precision are
        implicit in the key space."""
        raw = np.ascontiguousarray(tokens[:length], np.int32).tobytes()
        return (length, hashlib.sha1(raw).hexdigest())

    def _prefix_lookup(self, req: Request) -> Optional[SequenceSnapshot]:
        """Longest cached prefix STRICTLY below the request's prefill
        length, at chunk granularity — the final chunk always recomputes,
        so the hit path emits its first token through the same math as a
        cold prefill (token-identical by construction). With a fleet
        index attached, a local miss falls through to the shared
        host-RAM tier: a prefix evicted from this card's LRU (or a
        sibling's) faults back in instead of recomputing."""
        total = self._prefill_len(req)
        L = ((total - 1) // self.prefill_chunk) * self.prefill_chunk
        while L >= self.prefill_chunk:
            key = self._prefix_key(req.tokens, L)
            snap = self._prefix_cache.get(key)
            if snap is not None:
                self._prefix_cache.move_to_end(key)      # LRU touch
                return snap
            if self._prefix_index is not None:
                snap = self._prefix_index.host_get(key)
                if snap is not None:
                    self._prefix_store(key, snap)
                    self.telemetry.record_prefix_host_hit()
                    return snap
            L -= self.prefill_chunk
        return None

    def _prefix_store(self, key, snap: SequenceSnapshot) -> None:
        """Put one snapshot into the local LRU (dedup by content key,
        capacity-bounded) and keep the fleet index exact: inserts
        register this replica as a holder; local evictions deregister it
        AND park the evicted snapshot in the shared host-RAM tier
        (insert-on-evict), so the fleet keeps what this card dropped."""
        if key in self._prefix_cache:
            self._prefix_cache.move_to_end(key)
            return
        self._prefix_cache[key] = snap
        if self._prefix_index is not None:
            self._prefix_index.add(key, self._replica_id)
        while len(self._prefix_cache) > self.prefix_cache:
            old_key, old_snap = self._prefix_cache.popitem(last=False)
            if self._prefix_index is not None:
                self._prefix_index.discard(old_key, self._replica_id)
                self._prefix_index.host_insert(old_key, old_snap)

    def _prefix_insert(self, req: Request, slot: int) -> None:
        """Admit the slot's written prefix into the cache at a chunk
        boundary."""
        key = self._prefix_key(req.tokens, req.prefill_pos)
        if key in self._prefix_cache:
            self._prefix_cache.move_to_end(key)
            return
        self._prefix_store(key, self.snapshot_slot(slot, req.prefill_pos))

    # ---- fleet-shared prefix tier (ReplicaRouter hooks, PR 10) -----------
    def attach_prefix_index(self, index, replica_id: int) -> None:
        """Join a fleet-wide prefix tier: ``index`` is the router's
        ``FleetPrefixIndex``; every local insert/evict is mirrored there
        and local misses fault in from its shared host-RAM tier."""
        self._prefix_index = index
        self._replica_id = replica_id

    def prefix_keys(self, req: Request) -> List[Tuple[int, str]]:
        """Candidate prefix keys for a request, longest first — the
        router's steering probe. Same walk as ``_prefix_lookup`` (chunk
        multiples strictly below the prefill length) but against tokens
        only: any same-config replica produces identical keys, so the
        router can probe one replica and match holders fleet-wide."""
        if not self.prefix_cache or req.prefill_pos:
            return []
        total = self._prefill_len(req)
        L = ((total - 1) // self.prefill_chunk) * self.prefill_chunk
        out = []
        while L >= self.prefill_chunk:
            out.append(self._prefix_key(req.tokens, L))
            L -= self.prefill_chunk
        return out

    def prefix_snapshot(self, key) -> Optional[SequenceSnapshot]:
        """The holder side of a cross-replica ship: the local snapshot
        for ``key`` (LRU-touched — a prefix hot enough to ship is hot
        enough to keep), or None if this replica no longer holds it."""
        snap = self._prefix_cache.get(key)
        if snap is not None:
            self._prefix_cache.move_to_end(key)
        return snap

    def prefix_accept(self, key, snap: SequenceSnapshot) -> None:
        """The landing side of a cross-replica ship: a holder's snapshot
        enters THIS replica's local cache, so the request the router is
        about to submit here hits locally. Snapshots are host-side numpy
        keyed by content — same config means same leaf shapes, so a
        sibling's snapshot restores exactly like a local one."""
        self._prefix_store(key, snap)

    def export_prefix_cache(self) -> List[Tuple[Tuple[int, str],
                                                SequenceSnapshot]]:
        """Drain hook: the local cache's entries (oldest first). The
        snapshots are HOST-side state, so they outlive the card — the
        router parks them in the shared tier before purging this replica
        from the index."""
        return list(self._prefix_cache.items())

    # ---- host-RAM paging (consumer 2) ------------------------------------
    def _page_out_one(self) -> bool:
        """Park one active slot to host RAM so a fresh arrival can have
        its row — the engine's stand-in for the fleet's long-idle
        sessions.

        Victim policy (``page_victim``): the default ``"lru"`` picks the
        slot whose LAST DECODED token is oldest (ties to the lowest
        slot) — the session that has gone longest without progress is
        the one most likely idle, which is how a session cache actually
        ages. ``"remaining"`` keeps the pre-PR-10 policy: the session
        with the MOST tokens still to generate (ties to the highest
        slot), a service-time heuristic that can evict a hot session
        merely for being long."""
        if not self.states.active:
            return False

        def remaining(t: Ticket) -> int:
            req: Request = t.payload
            return req.max_new_tokens - len(req.output)

        if self.page_victim == "lru":
            slot = min(self.states.active,
                       key=lambda s: (self._last_decode.get(s, -1), s))
        else:
            slot = max(self.states.active,
                       key=lambda s: (remaining(self.states.active[s]), s))
        p = int(self.states.pos[slot])
        snap = self.snapshot_slot(slot, p, pos=p)
        t = self.states.page_out(slot)
        self._last_decode.pop(slot, None)
        self._paged[id(t)] = (t, snap)
        self.telemetry.record_paged_out()
        return True

    def _page_in(self) -> None:
        """Fault paged sessions back into whatever slots admission left
        free, oldest first; they rejoin the decode batch exactly where
        they left off (the restored row is the row that was parked)."""
        while self._paged and self.states.free_count > 0:
            _, (t, snap) = self._paged.popitem(last=False)
            slot = self.states.acquire(t)
            self.restore_slot(snap, slot)
            self.states.activate(t, slot, snap.pos)
            self._last_decode[slot] = self.telemetry.steps
            self.telemetry.record_paged_in()

    # ---- mid-prefill migration (consumer 3; ReplicaRouter hooks) ---------
    def migration_eligible(self, t: Ticket) -> bool:
        """The PR 4/5 steal-veto turned cost decision: a mid-prefill
        continuation MAY leave — with its snapshot — once it has at
        least ``migrate_min_tokens`` of completed chunk work to ship
        (below that, restarting costs no more than the round-trip)."""
        return (t.continuation and id(t) in self.states.prefilling
                and t.payload.prefill_pos >= max(self.migrate_min_tokens, 1))

    def export_prefill(self, t: Ticket) -> SequenceSnapshot:
        """Victim side of a migration: serialize the ticket's completed
        chunk prefix and free its slot (the state now travels with the
        ticket, so nothing is stranded)."""
        slot = self.states.prefilling[id(t)]
        snap = self.snapshot_slot(slot, t.payload.prefill_pos)
        self.states.release_prefilling(t)
        return snap

    def adopt_prefill(self, t: Ticket, snap: SequenceSnapshot) -> None:
        """Thief side: restore the snapshot into a free slot and park it —
        the continuation then chunks on from ``prefill_pos`` exactly as
        if it had always lived here (no restart-from-zero)."""
        slot = self.states.acquire(t)
        self.restore_slot(snap, slot)
        self.states.park(t, slot)
        self.telemetry.record_migrated()

    # ---- main loop ---------------------------------------------------------
    def _eff_len(self, req: Request) -> int:
        """Effective prefill length: what both admission sizing and bucket
        choice key on — they must agree or batch-formed groups split into
        multiple compiled dispatches."""
        return min(len(req.tokens), self.max_len - req.max_new_tokens - 1)

    def submit(self, req: Request, *, slo_ms: Optional[float] = None,
               priority: Optional[int] = None) -> Ticket:
        """Enqueue a request; keyword overrides beat the request's own
        slo/priority fields (router path). Returns the scheduler ticket —
        ``shed=True`` means admission control rejected it (the request is
        marked ``shed`` and will never be served).

        Prefix-cache hit admission: when the prompt's longest cached
        chunk-multiple prefix is found, the ticket enters the queue
        already sized to the REMAINING prefill (so feasibility shedding
        and the service estimator price the hit, not the full prompt)
        and carries a pending restore — the first chunk admission
        restores the snapshot into the acquired slot and prefill resumes
        at the prefix boundary."""
        hit = None
        if self.prefix_cache and not req.prefill_pos:
            hit = self._prefix_lookup(req)
        size = self._eff_len(req) - (hit.length if hit is not None else 0)
        t = self.scheduler.submit(
            req, size=max(size, 1),
            slo_ms=slo_ms if slo_ms is not None else req.slo_ms,
            priority=priority if priority is not None else req.priority)
        req.enqueue_t = t.enqueue_t
        req.shed = t.shed
        if hit is not None and not t.shed:
            req.prefill_pos = hit.length
            self._pending_restore[id(t)] = hit
            self.telemetry.record_prefix_hit()
        return t

    # ---- replica protocol (ReplicaRouter) --------------------------------
    @property
    def inflight(self) -> int:
        # paged sessions are admitted-but-unfinished work: they count
        # toward load even while their state sits in host RAM
        return self.states.inflight + len(self._paged)

    @property
    def free_slots(self) -> int:
        """Free slots — how many stolen tickets this replica could
        start right now (the router's steal admission cap). Paged
        sessions reserve their fault-back capacity: advertising their
        slots to thieves would let steals crowd out the page-in path."""
        return max(self.states.free_count - len(self._paged), 0)

    @property
    def has_work(self) -> bool:
        return bool(self.scheduler.depth or self.states.inflight
                    or self._paged)

    @property
    def cache_pressure(self) -> float:
        """Paging/cache pressure for the fleet controller: the host-RAM
        paging backlog per device slot. 0 = every admitted session has a
        row; 1.0 = a full extra batch of sessions is parked in host RAM
        waiting to fault back — sustained pressure means the fleet is
        serving more concurrent sessions than its slots can hold, which
        more replicas (not a bigger queue) fixes."""
        return len(self._paged) / max(self.batch_slots, 1)

    def steal_eligible(self, t: Ticket) -> bool:
        """Steal veto (router hook, delegated to the SequenceStateManager):
        continuations and mid-prefill tickets own a slot on THIS replica —
        moving one would strand the partially-written cache rows. Only
        fresh, not-yet-started tickets may leave. A prefix-cache hit
        with a pending restore is vetoed too — its snapshot lives in
        THIS engine's cache (a plain steal would strand the restored
        offset; migration is the path that ships state)."""
        return self.states.steal_eligible(t) \
            and id(t) not in self._pending_restore

    def drain_tickets(self) -> List[Ticket]:
        """Fault-drain hook (``ReplicaRouter.drain_replica``): hand back
        every accepted-but-unfinished ticket — the pending queue
        (continuations included) plus the in-flight decode batch — reset
        to fresh, because the KV state died with the card. Evicted
        requests restart from token zero on their new home; greedy decode
        regenerates the same output. Clears all slot state.

        Telemetry contract under a fault: counters measure work
        PERFORMED, not work delivered — the victim's prefills /
        total_tokens / TTFT samples for evicted work stand (that compute
        genuinely ran and its first token was genuinely emitted before
        the card died), and the surviving replica records the re-serve
        again. Only ``served`` stays delivery-exact: a ticket completes
        once. The wasted duplicate work is the measured cost of the
        fault."""
        out = self.scheduler.steal_pending(None, include_continuations=True)
        out.extend(self.states.evict_all())
        # paged sessions and pending prefix restores die with the card
        # too: their snapshots are host-side state of THIS replica
        out.extend(t for t, _ in self._paged.values())
        self._paged.clear()
        self._pending_restore.clear()
        self._last_decode.clear()
        for t in out:
            req: Request = t.payload
            req.output = []
            req.prefill_pos = 0
            req.done = False
            t.reset_fresh()
        return out

    def step_once(self):
        """One engine tick — the unified step. Chunked mode: at most ONE
        chunk group (a long prompt advances one chunk, or a group of
        short prompts prefills outright), then one decode step across
        the active batch — prefill work can stall decode slots for at
        most one chunk. Monolithic mode: refill every freed slot, then
        one decode step (the pre-chunking behaviour).

        Profiler spans, with constant names: ``serve.step`` around the
        tick; inside it ``serve.admit`` (scheduling and grouping),
        ``serve.prefill`` / ``serve.chunk`` (one group each) and
        ``serve.decode``; inside those ``serve.dispatch``
        (``StageExecutor.dispatch``) and ``serve.read`` (the blocking
        token readback)."""
        with TraceAnnotation("serve.step"):
            if self.prefill_chunk is not None:
                self._admit_chunk()
            else:
                self._admit()
            if self.page_host:
                # fault paged sessions back into whatever admission left
                # free (admission first: fresh arrivals take precedence
                # for slots, or page-in/page-out would thrash against
                # each other)
                self._page_in()
            self._step()

    def _admit(self):
        """Refill freed slots: admit up to len(free) tickets, group them by
        prefill bucket, and prefill each group in ONE bucketed call."""
        if self.page_host and not self.free and self.scheduler.fresh_depth:
            # slot-starved with fresh arrivals waiting: park one long-
            # idle active session to host RAM (one per tick — bounded
            # churn) so the arrival can prefill
            self._page_out_one()
        while self.free and self.scheduler.depth:
            with TraceAnnotation("serve.admit"):
                tickets = self.scheduler.admit(
                    min(len(self.free), self.max_prefill_batch))
                if not tickets:
                    return
                groups: Dict[int, List[Ticket]] = {}
                lens: Dict[int, List[int]] = {}
                for t in tickets:
                    req: Request = t.payload
                    L = self._eff_len(req)
                    b = pick_bucket(L, self.buckets)
                    groups.setdefault(b, []).append(t)
                    lens.setdefault(b, []).append(min(L, b))
            for b, group in groups.items():
                self._prefill_group(b, group, lens[b])

    def _prefill_group(self, bucket: int, group: List[Ticket],
                       lengths: List[int]):
        # pad the group to the next power of two (T5: static shapes, like
        # the buckets themselves): executables per bucket stay bounded at
        # log2(slots)+1 while wasted prefill compute stays under 2x — a
        # lone freed slot refills with a batch-1 call, not a batch-P one.
        # Padded rows carry zero tokens / length 1 and are discarded below.
        g = len(group)
        with TraceAnnotation("serve.prefill", bucket=bucket, rows=g):
            P = 1 << (g - 1).bit_length()
            toks = np.zeros((P, bucket), np.int32)
            lens = np.ones(P, np.int32)
            for j, (t, L) in enumerate(zip(group, lengths)):
                toks[j, :L] = t.payload.tokens[:L]
                lens[j] = L
            nxt, caches = self.executor.dispatch(
                "prefill", (bucket, P, self.precision),
                lambda: self._build_prefill(bucket),
                self.run_params, jnp.asarray(toks), jnp.asarray(lens))
            slots = [self.states.acquire(t) for t in group]
            self.caches = self.executor.dispatch(
                "slot_write", g, self._build_slot_write,
                self.caches, caches, jnp.asarray(slots, jnp.int32))
            with TraceAnnotation("serve.read"):
                nxt = np.asarray(nxt)
            now = time.perf_counter()
            for j, (t, slot, L) in enumerate(zip(group, slots, lengths)):
                t.payload.output.append(int(nxt[j]))
                t.payload.prefill_pos = L
                self.telemetry.record_ttft((now - t.enqueue_t) * 1e3)
                self.states.activate(t, slot, L)
                self._last_decode[slot] = self.telemetry.steps
            self.telemetry.prefills += g
            self.telemetry.prefill_batches += 1

    # ---- chunked prefill -------------------------------------------------
    def _prefill_len(self, req: Request) -> int:
        """Total tokens the chunked path must prefill — matches what the
        monolithic path would run (effective length, capped by the top
        bucket exactly like ``min(L, pick_bucket(L))`` caps it)."""
        return max(min(self._eff_len(req), self.buckets[-1]), 1)

    def _chunk_next_len(self, req: Request) -> int:
        return min(self.prefill_chunk,
                   self._prefill_len(req) - req.prefill_pos)

    def _chunk_bucket_of(self, t: Ticket) -> int:
        return pick_bucket(self._chunk_next_len(t.payload),
                           self.chunk_buckets)

    def _admit_chunk(self):
        """Chunked admission: ask the scheduler for ONE bucket-coherent
        chunk group (fresh tickets capped by free slots; continuations
        already hold theirs) and run it. Unfinished prompts re-enter the
        queue as continuation tickets; finished ones sample their first
        token and move to the decode batch."""
        if not self.scheduler.depth:
            return
        if self.page_host and not self.free and not self.prefilling \
                and self.scheduler.fresh_depth:
            self._page_out_one()        # same page-out rule as _admit
        if not self.free and not self.prefilling:
            return                      # every slot is decoding
        with TraceAnnotation("serve.admit"):
            group = self.scheduler.admit_coherent(
                self.batch_slots, bucket_fn=self._chunk_bucket_of,
                new_cap=len(self.free))
            if not group:
                return
            bucket = self._chunk_bucket_of(group[0])
        self._chunk_group(bucket, group)

    def _chunk_group(self, bucket: int, group: List[Ticket]):
        """Run one prompt chunk for every ticket in the group in a single
        full-batch dispatch, with K/V scattered at each row's own offset.
        Group rows may sit at different prefill offsets (request A's
        third chunk can batch with request B's first); slots outside the
        group ride along parked (zero tokens, dropped writes), exactly
        like idle rows ride a decode step."""
        g = len(group)
        with TraceAnnotation("serve.chunk", bucket=bucket, rows=g):
            P = 1 << (g - 1).bit_length()       # pad like _prefill_group
            toks = np.zeros((P, bucket), np.int32)
            start = np.zeros(P, np.int32)
            wpos = np.full(P, self.max_len, np.int32)   # padded: writes drop
            lens = np.zeros(P, np.int32)                # padded: rows drop
            last = np.zeros(P, np.int32)
            slots: List[int] = []
            for j, t in enumerate(group):
                req: Request = t.payload
                off = req.prefill_pos
                clen = min(self._chunk_next_len(req), bucket)
                slots.append(self.states.acquire(t))
                snap = self._pending_restore.pop(id(t), None)
                if snap is not None:
                    # prefix-cache hit: the cached prefix lands in the slot
                    # BEFORE this group's chunk dispatch reads the cache, so
                    # the chunk at offset ``off == snap.length`` attends a
                    # prefix identical to one it would have computed itself
                    self.restore_slot(snap, slots[-1])
                toks[j, :clen] = req.tokens[off:off + clen]
                start[j] = off
                wpos[j] = off
                lens[j] = clen
                last[j] = clen - 1
            slots_padded = np.asarray(slots + [slots[0]] * (P - g), np.int32)
            nxt, self.caches = self.executor.dispatch(
                "chunk_prefill", (bucket, P, self.precision),
                lambda: self._build_chunk(bucket),
                self.run_params, self.caches, jnp.asarray(slots_padded),
                jnp.asarray(toks), jnp.asarray(start), jnp.asarray(wpos),
                jnp.asarray(lens), jnp.asarray(last))
            with TraceAnnotation("serve.read"):
                nxt = np.asarray(nxt)
            now = time.perf_counter()
            for j, (t, slot) in enumerate(zip(group, slots)):
                req = t.payload
                req.prefill_pos += int(last[j]) + 1
                if self.prefix_cache \
                        and req.prefill_pos % self.prefill_chunk == 0:
                    # completed chunk boundary: admit the written prefix to
                    # the cache (content-keyed, so every request sharing a
                    # system prompt dedups onto one entry)
                    self._prefix_insert(req, slot)
                if req.prefill_pos >= self._prefill_len(req):
                    req.output.append(int(nxt[j]))
                    self.telemetry.record_ttft((now - t.enqueue_t) * 1e3)
                    self.telemetry.prefills += 1
                    self.states.activate(t, slot, req.prefill_pos)
                    self._last_decode[slot] = self.telemetry.steps
                else:
                    self.states.park(t, slot)
                    self.scheduler.resubmit(t, size=self._chunk_next_len(req))
            self.telemetry.prefill_batches += 1

    def _step(self):
        if not self.active:
            return
        with TraceAnnotation("serve.decode"):
            toks = np.zeros((self.batch_slots, 1), np.int32)
            # inactive rows (free or mid-chunked-prefill) still ride the
            # static-shape decode dispatch: their K/V write parks at
            # max_len-1 — a position no request ever attends (decoding stops
            # at max_len-1) — and the model layer freezes their per-slot
            # state under the active mask (a dummy step must not advance a
            # mid-prefill row's ring buffer or recurrent state)
            pos_vec = self.states.decode_positions(self.max_len - 1)
            active_mask = self.states.active_mask()
            for s, t in self.active.items():
                toks[s, 0] = t.payload.output[-1]
            nxt, self.caches = self.executor.dispatch(
                "decode", (self.precision,), self._build_decode,
                self.run_params, self.caches, jnp.asarray(toks),
                jnp.asarray(pos_vec), jnp.asarray(active_mask))
            with TraceAnnotation("serve.read"):
                nxt = np.asarray(nxt)
            self.telemetry.steps += 1
            for s in list(self.active):
                t = self.active[s]
                req: Request = t.payload
                self.pos[s] += 1
                req.output.append(int(nxt[s]))
                self.telemetry.total_tokens += 1
                self._last_decode[s] = self.telemetry.steps
                if len(req.output) >= req.max_new_tokens \
                        or self.pos[s] >= self.max_len - 1:
                    req.done = True
                    self.scheduler.complete(t)
                    # sync from the ticket, whose stamps are authoritative —
                    # rebase_pending (run_concurrent) may have shifted
                    # enqueue_t after submit stamped the request
                    req.enqueue_t = t.enqueue_t
                    req.finish_t = t.finish_t
                    self.states.release(s)
                    self._last_decode.pop(s, None)

    def run(self, requests: Sequence[Request]) -> List[Request]:
        for r in requests:
            self.submit(r)
        t0 = time.perf_counter()
        while self.has_work:
            self.step_once()
        self.telemetry.record_serving_window(time.perf_counter() - t0)
        return list(requests)


def make_replicas(cfg: ModelConfig, params, n: int,
                  precisions: Optional[Sequence[str]] = None,
                  quant_budget: float = 0.05,
                  devices: Optional[Sequence[jax.Device]] = None,
                  **engine_kw) -> List[InferenceEngine]:
    """N LM engine replicas sharing one set of weights (the paper's
    data-parallel deployment: same model on each card, distinct KV caches
    and runtime queues). Front with ``ReplicaRouter``.

    ``precisions`` gives each replica its own execution precision
    (``"fp32"`` / ``"w8a8"``) — the heterogeneous-fleet deployment where
    bulk traffic flows to int8 cards while accuracy-sensitive traffic
    pins to fp32 (the router's mixed-precision policy). The quantized
    weights are built ONCE and shared by every w8a8 replica.

    ``devices`` pins replica *i* to ``devices[i]`` with its own copy of
    the weights and its own caches — one card per replica, as deployed.
    Without it every replica shares the default device."""
    if precisions is None:
        precisions = ["fp32"] * n
    if len(precisions) != n:
        raise ValueError(f"precisions has {len(precisions)} entries for "
                         f"{n} replicas")
    if devices is None:
        devices = [None] * n
    if len(devices) != n:
        raise ValueError(f"devices has {len(devices)} entries for "
                         f"{n} replicas")
    qp = None
    if any(p == "w8a8" for p in precisions):
        from repro.models.quantize import build_quantized_params
        qp = build_quantized_params(cfg, params, budget=quant_budget)
    return [InferenceEngine(cfg, params, precision=p,
                            quantized_params=qp if p == "w8a8" else None,
                            device=d, **engine_kw)
            for p, d in zip(precisions, devices)]
