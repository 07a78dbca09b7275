"""LM cells: ``InferenceEngine`` (or ``replicas`` engines, replica *i* pinned
to device *i*, behind ``ReplicaRouter``) serving an open-loop mix.

Set-up makes the weights on the device from the seed in one jitted call,
builds the engines and serves warm-up requests (fixed contents, not the
window's) that touch every compiled shape the window can use: each prefill
bucket at each padded group size, each slot-write group size and the decode
step. The window then offers the mix's schedule; every request due in it
counts. After the window closes, the device's peak memory is read, the
engines are freed, and a sample of the finished requests is checked against
the plain float32 reference in ``bench/reference/lm.py``.

Times are the host clock at the end of each engine tick, when the tick's
tokens are on the host.
"""
from __future__ import annotations

import functools
import gc
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, workload
from bench.drivers.common import (CompileClock, TracedStretch, log,
                                  peak_bytes, percentile, sample_indices,
                                  span, stream_key)
from bench.reference import lm as ref

def program_config(cfg: dict):
    from repro.configs.base import ATTN_GLOBAL, ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        block_pattern=(ATTN_GLOBAL,), activation="silu", glu=True,
        norm_type="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False, rope_theta=cfg["rope_theta"],
        param_dtype=cfg["dtype"], activation_dtype=cfg["dtype"],
        attention_impl=cfg["engine"]["attention_impl"])


@functools.partial(jax.jit, static_argnames=("dm", "dtype"))
def make_weights(key, dm: counts.Dims, dtype: str):
    """Every weight, in the served type, from one key: normal with
    std 1/sqrt(fan-in) for projections and the head, 1 for the embedding,
    0.1 for the norms' gains around 1."""
    dt = jnp.dtype(dtype)
    L, d, H, K, hd, f, V = (dm.layers, dm.d, dm.heads, dm.kv_heads,
                            dm.head_dim, dm.ff, dm.vocab)
    shapes = {"embed": ((V, d), 1.0), "attn_norm": ((L, d), 0.1),
              "wq": ((L, d, H, hd), d ** -0.5),
              "wk": ((L, d, K, hd), d ** -0.5),
              "wv": ((L, d, K, hd), d ** -0.5),
              "wo": ((L, H, hd, d), (H * hd) ** -0.5),
              "mlp_norm": ((L, d), 0.1), "w_gate": ((L, d, f), d ** -0.5),
              "w_up": ((L, d, f), d ** -0.5), "w_down": ((L, f, d), f ** -0.5),
              "final_norm": ((d,), 0.1), "lm_head": ((V, d), d ** -0.5)}
    keys = jax.random.split(key, len(shapes))
    return {name: (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)
            for k, (name, (shape, std)) in zip(keys, sorted(shapes.items()))}


def program_params(w: dict, pcfg) -> dict:
    """The benchmark's weights in the engine's parameter tree (the same
    device buffers), checked against the tree the engine's own init
    would build."""
    from repro.models import model as model_mod
    p = {"embed": w["embed"],
         "scan": ({"pre_norm": {"scale": w["attn_norm"]},
                   "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                   "pre_mlp_norm": {"scale": w["mlp_norm"]},
                   "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}},),
         "tail": (), "final_norm": {"scale": w["final_norm"]},
         "lm_head": w["lm_head"]}
    want = jax.eval_shape(functools.partial(model_mod.init_params, pcfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), p)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise SystemExit("the engine's parameter tree no longer matches the "
                         "benchmark's weights")
    return p


@dataclass
class Rec:
    due: float
    req: object
    ticket: object = None
    times: List[float] = field(default_factory=list)
    done_t: Optional[float] = None


class Target:
    """One engine, or replicas behind the router, as one thing to tick."""

    def __init__(self, engines, router=None):
        self.engines, self.router = engines, router

    def submit(self, req):
        return (self.router or self.engines[0]).submit(req)

    @property
    def has_work(self) -> bool:
        return (self.router or self.engines[0]).has_work

    def tick(self):
        """One engine step, or one round of the router's
        ``run_until_drained`` loop (each live replica with work steps
        once), so that arrivals keep coming between rounds."""
        if self.router is None:
            self.engines[0].step_once()
            return
        r = self.router
        r.maybe_steal()
        for i, e in enumerate(r.replicas):
            if not r.dead[i] and e.has_work:
                s0 = time.perf_counter()
                e.step_once()
                r.record_dispatch(i, time.perf_counter() - s0)

    @property
    def prefill_batches(self) -> int:
        return sum(e.telemetry.prefill_batches for e in self.engines)


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cfg
        self.mix = ctx.traffic
        self.dm = counts.Dims.of(self.cfg)
        self.eng_kw = {k: (tuple(v) if isinstance(v, list) else v)
                       for k, v in self.cfg["engine"].items()
                       if k != "attention_impl"}
        self.buckets = tuple(self.eng_kw["prefill_buckets"])
        self.devices = jax.devices()[:ctx.chips]

    # ---- set-up ------------------------------------------------------------
    def build(self, seed: int):
        from repro.serving.engine import InferenceEngine, make_replicas
        from repro.serving.router import ReplicaRouter
        pcfg = program_config(self.cfg)
        with jax.default_device(self.devices[0]):
            self.w = make_weights(stream_key(seed), self.dm, self.cfg["dtype"])
        params = program_params(self.w, pcfg)
        n = self.cfg.get("replicas", 1)
        if n == 1:
            self.target = Target([InferenceEngine(pcfg, params,
                                                  **self.eng_kw)])
        else:
            if len(self.devices) < n:
                raise SystemExit(f"{n} replicas need {n} devices")
            kw = dict(self.eng_kw)
            prec = kw.pop("precision", "fp32")
            engines = make_replicas(pcfg, params, n, precisions=[prec] * n,
                                    devices=self.devices[:n], **kw)
            self.target = Target(engines, ReplicaRouter(engines))
            held = {d for e in engines
                    for d in jax.tree.leaves(e.caches)[0].devices()}
            log(f"replica devices: {len(held)} distinct")

    def warm(self):
        """Serve fixed requests that compile every shape the window can
        use, on every replica."""
        slots = self.eng_kw["batch_slots"]
        # a group of g prefills as 2^ceil(log2 g) rows: one group size per
        # padded size reaches every (bucket, rows) program, and every other
        # g its own slot write
        padded = {}
        for g in range(1, slots + 1):
            padded[1 << (g - 1).bit_length()] = g
        groups = [(b, g) for b in self.buckets for g in padded.values()]
        groups += [(self.buckets[0], g) for g in range(1, slots + 1)
                   if g not in padded.values()]
        from repro.serving.engine import Request
        r = np.random.default_rng(0)
        for e in self.target.engines:
            for b, g in groups:
                for j in range(g):
                    e.submit(Request(-1, r.integers(0, self.dm.vocab, b)
                                     .astype(np.int32), max_new_tokens=2))
                while e.has_work:
                    e.step_once()
        jax.block_until_ready(self.target.engines[0].caches)

    # ---- the window --------------------------------------------------------
    def prefill_len(self, req) -> int:
        return min(len(req.tokens), self.buckets[-1],
                   self.eng_kw["max_len"] - req.max_new_tokens - 1)

    def window(self, seed: int, seconds: float, trace_dir=None,
               trace_s: float = 2.0) -> dict:
        from repro.serving.engine import Request
        sched = workload.lm_open_loop(self.mix, seed, seconds, self.dm.vocab)
        recs = [Rec(0.0, Request(i, s.prompt, max_new_tokens=s.max_new))
                for i, s in enumerate(sched)]
        live: List[Rec] = []
        ticks = []
        tr = TracedStretch(trace_dir)
        traced_from = trace_t0 = None
        target = self.target
        i = 0
        with CompileClock() as cc:
            t_open = time.perf_counter()
            close = t_open + seconds
            for r, s in zip(recs, sched):
                r.due = t_open + s.due_s
            while True:
                now = time.perf_counter()
                if now >= close:
                    break
                if trace_dir and not tr.active and now >= close - trace_s:
                    tr.start()
                    traced_from, trace_t0 = len(ticks), now
                while i < len(recs) and recs[i].due <= now:
                    with span("bench.submit"):
                        recs[i].ticket = target.submit(recs[i].req)
                    live.append(recs[i])
                    i += 1
                if not target.has_work:
                    nxt = recs[i].due if i < len(recs) else close
                    with span("bench.wait"):
                        time.sleep(max(min(nxt, close) - now, 0.0))
                    continue
                before = [len(r.req.output) for r in live]
                pb = target.prefill_batches
                steps = [e.telemetry.steps for e in target.engines]
                t0 = time.perf_counter()
                with span("bench.step_once"):
                    target.tick()
                t1 = time.perf_counter()
                prompts, contexts = [], []
                for r, k0 in zip(live, before):
                    k1 = len(r.req.output)
                    r.times.extend([t1] * (k1 - k0))
                    if k0 == 0 and k1 > 0:
                        prompts.append(self.prefill_len(r.req))
                    n = self.prefill_len(r.req)
                    contexts.extend(n + k for k in range(max(k0, 1), k1))
                    if r.req.done:
                        r.done_t = t1
                live = [r for r in live if not r.req.done]
                ticks.append({"t0": t0, "t1": t1,
                              "prefill": target.prefill_batches > pb,
                              "prompts": prompts, "contexts": contexts,
                              "decode_steps": sum(
                                  e.telemetry.steps - s0 for e, s0
                                  in zip(target.engines, steps))})
            compiles = cc.count
        tr.stop()
        return {"recs": recs, "ticks": ticks, "open": t_open, "close": close,
                "seconds": seconds, "compiles": compiles,
                "traced_from": traced_from if tr.done else None,
                "untraced_until": trace_t0 if tr.done else close,
                "trace_dir": trace_dir if tr.done else None}

    # ---- after the window ----------------------------------------------------
    def free(self):
        del self.target
        gc.collect()

    def check(self, seed: int, win: dict, control: bool = False) -> dict:
        """Teacher-forced reference over a sample of finished requests: the
        widest gap between a served token's logit and the best one."""
        chk = self.cfg["check"]
        done = [r for r in win["recs"]
                if r.done_t is not None and r.done_t <= win["close"]]
        if not done:
            return {"tokens": 0, "requests": 0, "gap": float("inf")}
        longest = max(range(len(done)), key=lambda j: len(done[j].req.tokens)
                      + len(done[j].req.output))
        order = sample_indices(workload.rng(seed, workload.CHECK), len(done),
                               chk["requests"], longest)
        picked, n_tok = [], 0
        for j in order:
            if n_tok >= chk["tokens"]:
                break
            picked.append(done[j])
            n_tok += len(done[j].req.output)
        B, S, R = chk["requests"], chk["seq_len"], chk["positions"]
        tokens = np.zeros((B, S), np.int32)
        read = np.zeros((B, R), np.int32)
        served = np.zeros((B, R), np.int32)
        mask = np.zeros((B, R), bool)
        for b, r in enumerate(picked):
            n = self.prefill_len(r.req)
            out = np.asarray(r.req.output, np.int32)
            seq = np.concatenate([r.req.tokens[:n], out[:-1]])
            if len(seq) > S or len(out) > R:
                raise SystemExit(f"check sizes too small for request "
                                 f"{r.req.rid}: {len(seq)} > {S} or "
                                 f"{len(out)} > {R}")
            tokens[b, :len(seq)] = seq
            read[b, :len(out)] = n - 1 + np.arange(len(out))
            served[b, :len(out)] = out
            mask[b, :len(out)] = True
        kw = dict(eps=self.cfg["rms_norm_eps"], theta=self.cfg["rope_theta"])
        logits = ref.logits_at(self.w, tokens, read, **kw)
        gaps = np.asarray(ref.served_gaps(logits, jnp.asarray(served)))
        out = {"tokens": int(mask.sum()), "requests": len(picked),
               "gap": float(gaps[mask].max()),
               "mismatches": int((gaps[mask] > 0).sum())}
        if control:
            ctl = ref.logits_at(self.w, tokens, read, quant="int8", **kw)
            cg = np.asarray(ref.control_gaps(logits, ctl))
            out["control_gap"] = float(cg[mask].max())
        return out


def end_to_end(win: dict) -> dict:
    close, secs = win["close"], win["seconds"]
    due = [r for r in win["recs"] if r.due < close]
    ttft, itl = [], []
    for r in due:
        first = [t for t in r.times if t <= close]
        ttft.append(((first[0] if first else close) - r.due) * 1e3)
        itl.extend(np.diff(first) * 1e3)
    completed = sum(1 for r in due if r.done_t is not None
                    and r.done_t <= close)
    return {"ttft_p95_ms": percentile(ttft, 0.95),
            "itl_p95_ms": percentile(itl, 0.95),
            "completed_rps": completed / secs,
            "ttft_p50_ms": percentile(ttft, 0.5),
            "itl_p50_ms": percentile(itl, 0.5),
            "attempted": len(due), "completed": completed,
            "shed": sum(1 for r in due if r.ticket is not None
                        and r.ticket.shed),
            "gaps": len(itl)}


def run(ctx) -> dict:
    # every program this cell compiles is kept, so only a checkout's first
    # run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = Cell(ctx)
    cell.build(ctx.seed)
    cell.warm()
    setup_s = time.time() - ctx.t_start
    log(f"set-up {setup_s:.3f} s")
    win = cell.window(ctx.seed, ctx.seconds, ctx.trace_dir)
    e2e = end_to_end(win)
    log(f"window: {e2e['attempted']} requests due, {e2e['completed']} "
        f"completed, {len(win['ticks'])} ticks, {win['compiles']} compiles; "
        f"ttft p50 {e2e['ttft_p50_ms']:.3f} ms, itl p50 "
        f"{e2e['itl_p50_ms']:.3f} ms over {e2e['gaps']} gaps")
    mem = peak_bytes(cell.devices)
    cell.free()
    t = time.perf_counter()
    chk = cell.check(ctx.seed, win)
    log(f"check: {chk['tokens']} tokens of {chk['requests']} requests, "
        f"{chk.get('mismatches', 0)} not the reference's first choice, "
        f"in {time.perf_counter() - t:.3f} s")
    limit = ctx.cfg["check"]["gap_limit"]
    checks = {"logit_gap": (chk["gap"], limit),
              "window_compiles": (win["compiles"], 0)}
    return {"setup_s": setup_s, "e2e": e2e, "win": win, "checks": checks,
            "correct": chk["gap"] <= limit and win["compiles"] == 0
            and chk["tokens"] > 0,
            "attempted": e2e["attempted"], "failed": e2e["shed"],
            "memory_peak_bytes": mem, "dims": cell.dm}
