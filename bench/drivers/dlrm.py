"""DLRM cells: ``DLRMEngine`` ranking requests of many items each.

Set-up makes the tables on the device from the seed, in the served layout
(int8 rows with an fp16 scale and bias, one slab in table order), in one
jitted call that writes the slab block by block, and the float32 MLPs beside
them. It serves one warm-up request from a stream of its own, never one of
the window's: the ingest path compiles programs whose shapes follow a
request's contents, and users pay those compiles, so they stay in the window.
JAX's persistent cache keeps only programs that took a second or more to
compile, so every run of a cell finds the same programs cached.

A closed loop keeps ``callers`` requests outstanding; each caller sends its
next request as soon as its scores are on the host. An open loop sends on
the mix's schedule. After the window, the device's peak memory is read and a
sample of the answered requests, drawn from the seed, is compared with the
float64 reference in ``bench/reference/dlrm.py``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import workload
from bench.drivers.common import (CompileClock, TracedStretch, ceil_div,
                                  log, peak_bytes, percentile,
                                  sample_indices, span, stream_key)
from bench.reference import dlrm as ref

ROW_HALF_WIDTH = 0.177      # rows ~ uniform(-a, a): std 1/sqrt(96)


def program_config(cfg: dict):
    from repro.configs.dlrm_paper import DLRMConfig
    return DLRMConfig(
        name=cfg["name"], num_dense_features=cfg["num_dense_features"],
        table_rows=tuple(cfg["table_rows"]), embed_dim=cfg["embedding_dim"],
        avg_lookups_per_table=tuple(cfg["avg_lookups_per_table"]),
        max_lookups_per_table=cfg["max_lookups_per_table"],
        bottom_mlp=tuple(cfg["bottom_mlp"]), top_mlp=tuple(cfg["top_mlp"]))


@functools.partial(jax.jit, static_argnames=("rows", "dim", "block"))
def make_tables(key, rows: int, dim: int, block: int):
    """The slab (rows, dim) of uint8 codes, written block by block, and a
    per-row fp16 scale and bias: row = code * scale + bias."""
    block = min(block, rows)
    kq, ks, kb = jax.random.split(key, 3)

    def body(i, q):
        start = jnp.minimum(i * block, rows - block)
        blk = jax.random.bits(jax.random.fold_in(kq, i), (block, dim),
                              jnp.uint8)
        return jax.lax.dynamic_update_slice(q, blk, (start, 0))

    q = jax.lax.fori_loop(0, ceil_div(rows, block), body,
                          jnp.zeros((rows, dim), jnp.uint8))
    scale = 2 * ROW_HALF_WIDTH / 255 \
        * jax.random.uniform(ks, (rows,), jnp.float32, 0.5, 1.5)
    bias = -0.5 * 255 * scale \
        + 0.01 * jax.random.normal(kb, (rows,), jnp.float32)
    return {"q8": q, "scale": scale.astype(jnp.float16),
            "bias": bias.astype(jnp.float16)}


@functools.partial(jax.jit, static_argnames=("dims",))
def make_mlp(key, dims):
    out = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        out.append({"w": jax.random.normal(kw, (a, b), jnp.float32) * a ** -0.5,
                    "b": 0.01 * jax.random.normal(kb, (b,), jnp.float32)})
    return out


@jax.jit
def _gather(slab, gidx):
    return (jnp.take(slab["q8"], gidx, axis=0),
            jnp.take(slab["scale"], gidx, axis=0),
            jnp.take(slab["bias"], gidx, axis=0))


@dataclass
class Rec:
    index: int
    sent: float
    batch: dict
    ticket: object = None
    done_t: Optional[float] = None
    scores: Optional[np.ndarray] = None


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cfg
        self.mix = ctx.traffic
        self.device = jax.devices()[0]
        rows = np.asarray(self.cfg["table_rows"], np.int64)
        self.offsets = np.concatenate([[0], np.cumsum(rows)[:-1]])
        self.total_rows = ceil_div(int(rows.sum()), 8) * 8

    def build(self, seed: int):
        from repro.models import dlrm as dlrm_mod
        from repro.serving.dlrm_engine import DLRMEngine
        eng = dict(self.cfg["engine"])
        jax.config.update("jax_default_matmul_precision",
                          eng.pop("matmul_precision"))
        pcfg = program_config(self.cfg)
        asn = dlrm_mod.make_assignment(pcfg, 1)
        key = stream_key(seed)
        self.slab = make_tables(jax.random.fold_in(key, 0), self.total_rows,
                                self.cfg["embedding_dim"],
                                self.cfg["table_block_rows"])
        n = len(self.cfg["table_rows"])
        bottom = (self.cfg["num_dense_features"], *self.cfg["bottom_mlp"])
        top = (self.cfg["bottom_mlp"][-1] + (n + 1) * n // 2,
               *self.cfg["top_mlp"])
        self.mlps = {"bottom": make_mlp(jax.random.fold_in(key, 1), bottom),
                     "top": make_mlp(jax.random.fold_in(key, 2), top)}
        params = {"slab_q": self.slab, **self.mlps}
        self.engine = DLRMEngine(pcfg, asn, params, **eng)

    def warm(self):
        b = workload.dlrm_request(self.mix, self.cfg, 0, 0, workload.WARM)
        self.engine.submit(b)
        outs = self.engine.step_once()
        np.asarray(outs[0])

    # ---- the window --------------------------------------------------------
    def window(self, seed: int, seconds: float, trace_dir=None,
               trace_at: float = 0.4) -> dict:
        eng = self.engine
        recs: List[Rec] = []
        steps = []
        tr = TracedStretch(trace_dir)
        traced = []
        closed = self.mix["loop"] == "closed"
        sched = None
        if not closed:
            n = max(int(round(self.mix["arrivals"]["rate_per_s"]
                              * seconds)), 1)
            sched = workload.open_loop_due(self.mix["arrivals"], n)
        pending: List[Rec] = []
        tr_t0 = None

        def send(now):
            r = Rec(len(recs), now, workload.dlrm_request(
                self.mix, self.cfg, seed, len(recs)))
            with span("bench.submit"):
                r.ticket = eng.submit(r.batch)
            recs.append(r)
            pending.append(r)

        with CompileClock() as cc:
            t_open = time.perf_counter()
            close = t_open + seconds
            if closed:
                for _ in range(self.mix["callers"]):
                    send(t_open)
            trace_from = t_open + trace_at * seconds
            while True:
                now = time.perf_counter()
                if now >= close:
                    break
                if not closed:
                    while len(recs) < len(sched) \
                            and t_open + sched[len(recs)] <= now:
                        send(t_open + sched[len(recs)])
                if not eng.has_work:
                    nxt = t_open + sched[len(recs)] \
                        if not closed and len(recs) < len(sched) else close
                    with span("bench.wait"):
                        time.sleep(max(min(nxt, close) - now, 0.0))
                    continue
                if trace_dir and not tr.done and now >= trace_from:
                    tr.start()              # one whole step is traced
                    tr_t0 = now
                c0 = cc.count
                t0 = time.perf_counter()
                with span("bench.step_once"):
                    outs = eng.step_once()
                with span("bench.readback"):
                    host = [np.asarray(o) for o in outs]
                t1 = time.perf_counter()
                done = sorted((r for r in pending if r.ticket.finish_t),
                              key=lambda r: (r.ticket.finish_t, r.index))
                for r, s in zip(done, host):
                    r.done_t, r.scores = t1, s
                pending = [r for r in pending if r.done_t is None]
                if tr.active:
                    traced.append(len(steps))
                    tr.stop()
                steps.append({"t0": t0, "t1": t1,
                              "items": sum(len(r.batch["lengths"])
                                           for r in done),
                              "lookups": sum(int(r.batch["lengths"].sum())
                                             for r in done),
                              "requests": len(done),
                              "compiles": cc.count - c0})
                if closed:
                    for _ in done:
                        send(t1)
            compiles = cc.count
        tr.stop()
        return {"recs": recs, "steps": steps, "open": t_open, "close": close,
                "seconds": seconds, "compiles": compiles,
                "traced_steps": traced if tr.done else [],
                "untraced_until": tr_t0 if tr.done else close,
                "trace_dir": trace_dir if tr.done else None}

    # ---- after the window --------------------------------------------------
    def free(self):
        del self.engine

    def check(self, seed: int, win: dict, control: bool = False) -> dict:
        chk = self.cfg["check"]
        done = [r for r in win["recs"]
                if r.done_t is not None and r.done_t <= win["close"]]
        if not done:
            return {"requests": 0, "error": float("inf")}
        order = sample_indices(workload.rng(seed, workload.CHECK), len(done),
                               chk["requests"], 0)
        bottom = [(np.asarray(l["w"]), np.asarray(l["b"]))
                  for l in self.mlps["bottom"]]
        top = [(np.asarray(l["w"]), np.asarray(l["b"]))
               for l in self.mlps["top"]]
        worst = worst_ctl = 0.0
        for j in order:
            r = done[j]
            b = r.batch
            gidx = b["indices"] + self.offsets[None, :, None]
            q, s, bias = (np.asarray(a) for a in
                          _gather(self.slab, jnp.asarray(gidx, jnp.int32)))
            pooled = ref.pool(q, s, bias, b["lengths"])
            want = ref.scores(pooled, b["dense"], bottom, top)
            worst = max(worst, ref.worst_error(r.scores, want))
            if control:
                got = ref.scores(pooled, b["dense"], bottom, top,
                                 precision="high")
                worst_ctl = max(worst_ctl, ref.worst_error(got, want))
        out = {"requests": len(order), "error": worst}
        if control:
            out["control_error"] = worst_ctl
        return out


def end_to_end(win: dict) -> dict:
    close = win["close"]
    lat = [((r.done_t if r.done_t is not None and r.done_t <= close
             else close) - r.sent) * 1e3 for r in win["recs"]]
    completed = sum(1 for r in win["recs"]
                    if r.done_t is not None and r.done_t <= close)
    return {"rank_p95_ms": percentile(lat, 0.95),
            "rank_p50_ms": percentile(lat, 0.5),
            "completed_rps": completed / win["seconds"],
            "attempted": len(win["recs"]), "completed": completed,
            "shed": sum(1 for r in win["recs"] if r.ticket.shed)}


def run(ctx) -> dict:
    # only programs that take a second or more to compile are kept: the
    # ingest's tiny programs, shaped by request contents, compile in every
    # run, as they do for a server that meets fresh requests
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    cell = Cell(ctx)
    cell.build(ctx.seed)
    cell.warm()
    setup_s = time.time() - ctx.t_start
    log(f"set-up {setup_s:.3f} s")
    win = cell.window(ctx.seed, ctx.seconds, ctx.trace_dir)
    e2e = end_to_end(win)
    log(f"window: {e2e['attempted']} requests sent, {e2e['completed']} "
        f"answered, {len(win['steps'])} steps, {win['compiles']} compiles "
        f"({[s['compiles'] for s in win['steps']]} by step); rank p50 "
        f"{e2e['rank_p50_ms']:.3f} ms")
    mem = peak_bytes([cell.device])
    cell.free()
    t = time.perf_counter()
    chk = cell.check(ctx.seed, win)
    log(f"check: {chk['requests']} requests in "
        f"{time.perf_counter() - t:.3f} s")
    limit = ctx.cfg["check"]["error_limit"]
    return {"setup_s": setup_s, "e2e": e2e, "win": win,
            "checks": {"score_error": (chk["error"], limit)},
            "correct": chk["error"] <= limit,
            "attempted": e2e["attempted"], "failed": e2e["shed"],
            "memory_peak_bytes": mem}
