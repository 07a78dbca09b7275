"""DLRM-DCNv2 cells: ``DLRMEngine`` with the low-rank cross network.

Set-up, warm-up and tracing are ``bench/drivers/dlrm.py``'s ``Cell``;
this module makes the cross layers beside its tables and MLPs, serves them
through the configuration's ``interaction: "dcn"``, and checks a sample of
the answered requests against the float64 reference in
``bench/reference/dlrm_dcn.py``.

The window differs in two things. The callers' requests are drawn in
set-up, ``POOL`` of them from the seed by ``workload.dlrm_request``, and
request ``i`` of the window is ``pool[i % POOL]``: drawn inside the window,
as ``dlrm.py`` draws them, 26 x 160 x 100 Zipf ids take the host 60-85 ms
a request, three quarters of each step at this cell's widths, and the
window would time the load generator, not the server. And a step's scores
come back in one ``jax.device_get``, not one transfer per request.

The ingest compiles its unpack for every staged-width bucket on the warm-up
request, so the window compiles nothing (``dlrm.window_compiles``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import workload
from bench.drivers import dlrm as base
from bench.drivers.common import (CompileClock, TracedStretch, log,
                                  peak_bytes, sample_indices, span,
                                  stream_key)
from bench.reference import dlrm_dcn as ref

POOL = 64           # requests drawn in set-up, cycled through the window


def program_config(cfg: dict):
    """The program's ``DLRMConfig``; a program without the cross network
    refuses the keys here, before anything is built."""
    return dataclasses.replace(base.program_config(cfg), interaction="dcn",
                               dcn_num_layers=cfg["dcn_num_layers"],
                               dcn_low_rank_dim=cfg["dcn_low_rank_dim"])


@functools.partial(jax.jit, static_argnames=("d", "r", "layers"))
def make_cross(key, d: int, r: int, layers: int):
    """V (d, r) and W (r, d), normal with std 1/sqrt(fan-in); b (d,)."""
    out = []
    for i in range(layers):
        kv, kw, kb = jax.random.split(jax.random.fold_in(key, i), 3)
        out.append({
            "v": jax.random.normal(kv, (d, r), jnp.float32) * d ** -0.5,
            "w": jax.random.normal(kw, (r, d), jnp.float32) * r ** -0.5,
            "b": 0.01 * jax.random.normal(kb, (d,), jnp.float32)})
    return out


class Cell(base.Cell):
    def build(self, seed: int):
        pcfg = program_config(self.cfg)
        from repro.models import dlrm as dlrm_mod
        from repro.serving.dlrm_engine import DLRMEngine
        eng = dict(self.cfg["engine"])
        jax.config.update("jax_default_matmul_precision",
                          eng.pop("matmul_precision"))
        asn = dlrm_mod.make_assignment(pcfg, 1)
        key = stream_key(seed)
        self.slab = base.make_tables(jax.random.fold_in(key, 0),
                                     self.total_rows, pcfg.embed_dim,
                                     self.cfg["table_block_rows"])
        bottom = (pcfg.num_dense_features, *pcfg.bottom_mlp)
        top = (pcfg.cross_dim, *pcfg.top_mlp)
        self.mlps = {
            "bottom": base.make_mlp(jax.random.fold_in(key, 1), bottom),
            "top": base.make_mlp(jax.random.fold_in(key, 2), top),
            "cross": make_cross(jax.random.fold_in(key, 3), pcfg.cross_dim,
                                pcfg.dcn_low_rank_dim, pcfg.dcn_num_layers)}
        self.engine = DLRMEngine(pcfg, asn, {"slab_q": self.slab,
                                             **self.mlps}, **eng)

    def warm(self):
        """Warm-up, then the window's requests, drawn from the run's seed
        before the window opens."""
        super().warm()
        self.pool = [workload.dlrm_request(self.mix, self.cfg,
                                           self.ctx.seed, i)
                     for i in range(POOL)]

    def window(self, seed: int, seconds: float, trace_dir=None,
               trace_at: float = 0.4) -> dict:
        """``dlrm.py``'s window, with requests from the pool and one
        readback a step."""
        eng = self.engine
        recs: List[base.Rec] = []
        pending: List[base.Rec] = []
        steps, traced = [], []
        tr = TracedStretch(trace_dir)
        closed = self.mix["loop"] == "closed"
        sched = None
        if not closed:
            n = max(int(round(self.mix["arrivals"]["rate_per_s"]
                              * seconds)), 1)
            sched = workload.open_loop_due(self.mix["arrivals"], n)
        tr_t0 = None

        def send(now):
            r = base.Rec(len(recs), now, self.pool[len(recs) % POOL])
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
                    host = jax.device_get(outs)     # one transfer
                t1 = time.perf_counter()
                done = sorted((r for r in pending if r.ticket.finish_t),
                              key=lambda r: (r.ticket.finish_t, r.index))
                for r, sc in zip(done, host):
                    r.done_t, r.scores = t1, sc
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

    def check(self, seed: int, win: dict, control: bool = False) -> dict:
        done = [r for r in win["recs"]
                if r.done_t is not None and r.done_t <= win["close"]]
        if not done:
            return {"requests": 0, "error": float("inf")}
        order = sample_indices(workload.rng(seed, workload.CHECK), len(done),
                               self.cfg["check"]["requests"], 0)
        host = jax.device_get(self.mlps)
        bottom = [(l["w"], l["b"]) for l in host["bottom"]]
        top = [(l["w"], l["b"]) for l in host["top"]]
        cross = [(l["v"], l["w"], l["b"]) for l in host["cross"]]
        worst = worst_ctl = 0.0
        for j in order:
            r = done[j]
            b = r.batch
            gidx = b["indices"] + self.offsets[None, :, None]
            q, s, bias = (np.asarray(a) for a in base._gather(
                self.slab, jnp.asarray(gidx, jnp.int32)))
            pooled = ref.pool(q, s, bias, b["lengths"])
            want = ref.scores(pooled, b["dense"], bottom, cross, top)
            worst = max(worst, ref.worst_error(r.scores, want))
            if control:
                got = ref.scores(pooled, b["dense"], bottom, cross, top,
                                 precision="high")
                worst_ctl = max(worst_ctl, ref.worst_error(got, want))
        out = {"requests": len(order), "error": worst}
        if control:
            out["control_error"] = worst_ctl
        return out


def run(ctx) -> dict:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    cell = Cell(ctx)
    cell.build(ctx.seed)
    cell.warm()
    setup_s = time.time() - ctx.t_start
    log(f"set-up {setup_s:.3f} s")
    win = cell.window(ctx.seed, ctx.seconds, ctx.trace_dir)
    e2e = base.end_to_end(win)
    log(f"window: {e2e['attempted']} requests sent, {e2e['completed']} "
        f"answered, {len(win['steps'])} steps, {win['compiles']} compiles; "
        f"rank p50 {e2e['rank_p50_ms']:.3f} ms, p95 "
        f"{e2e['rank_p95_ms']:.3f} ms")
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
