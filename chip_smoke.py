#!/usr/bin/env python3
"""Chip smoke test: both serving engines, end to end, on a TPU.

    python chip_smoke.py               # one chip: LM phase, then DLRM phase
    python chip_smoke.py --four-chips  # four chips: pinned LM replicas only

Everything runs in this one process: a TPU belongs to one process at a
time, so no phase starts a child.

One chip, in order:

1. Device check. Prints the device kind and count, the JAX version and
   the compile-cache directory, and fails unless the platform is ``tpu``.
2. LM. deepseek-7b at its published widths (d_model 4096, 32x128 MHA,
   d_ff 11008, vocab 102400, bf16) cut to 16 of its 30 layers, random
   weights from a seed, initialised inside ``jax.jit`` on the device.
   16 requests (prompts of 8-512 tokens, 32 new tokens each) go through
   ``InferenceEngine.run`` with 8 slots, ``max_len`` 1024 and prefill
   buckets 32-512, as ``launch/serve.py`` serves them. Prefill runs the
   flash-attention Pallas kernel (``attention_impl="flash_pallas"``).
3. LM reference. For the requests with the shortest and the longest
   prompt, every generated token is checked against a plain
   full-sequence forward (no cache, jnp attention) over prompt plus the
   previous tokens. The engine's token must score within ``GAP_TOL``
   reference-logit standard deviations of the reference argmax; exact
   agreement is not required because bf16 rounding differs between the
   cached and uncached paths and can flip a near tie.
4. DLRM. ``DLRMEngine`` at PAPER_COMPLEX's published widths (96 tables,
   embed_dim 96, bottom MLP 1024-512-96, top MLP 2048-2048-1024-512-1,
   128 lookups per table, int8 rows) with the rows cut to 8 M in all,
   power-law over the tables. 8 batches of 64 go through
   ``DLRMEngine.serve`` as ``launch/serve.py`` serves them; every output
   is checked against a float64 numpy reference of the same model.

With ``--four-chips``: 4 LM replicas, replica *i* pinned to
``jax.devices()[i]`` with its own weights and caches, behind
``ReplicaRouter``; the same trace is served by one replica on device 0,
and every request's tokens must be identical between the two runs.

Any failed phase raises, so the script exits non-zero without the result
line. The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time
from typing import List

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.configs import get_config, reduce_for_smoke        # noqa: E402
from repro.configs import dlrm_paper                          # noqa: E402
from repro.configs.dlrm_paper import DLRMConfig               # noqa: E402
from repro.configs.base import ModelConfig                    # noqa: E402
from repro.core.backend import (compile_cache_dir,            # noqa: E402
                                configure_compile_cache)
from repro.data.synthetic import dlrm_batches                 # noqa: E402
from repro.models import dlrm as dlrm_mod                     # noqa: E402
from repro.models import model as model_mod                   # noqa: E402
from repro.serving.dlrm_engine import DLRMEngine              # noqa: E402
from repro.serving.engine import (InferenceEngine, Request,   # noqa: E402
                                  make_replicas)
from repro.serving.router import ReplicaRouter                # noqa: E402

SEED = 0
# engine token vs reference argmax: largest accepted logit shortfall, in
# standard deviations of the reference logits at that position
GAP_TOL = 0.25
# DLRM outputs vs the float64 reference: largest accepted error, as a
# fraction of the largest reference |logit| in the batch (the chip runs
# float32 matmuls as bf16 passes)
DLRM_TOL = 0.05


@dataclasses.dataclass(frozen=True)
class Sizes:
    lm: ModelConfig
    slots: int
    max_len: int
    buckets: tuple
    requests: int
    prompt_len: tuple          # (shortest, longest) prompt
    new_tokens: int
    dlrm: DLRMConfig
    dlrm_batches: int
    dlrm_batch: int


def full_sizes() -> Sizes:
    """The chip run: published widths, depth and table rows cut to fit
    one 16 GB v5e."""
    lm = dataclasses.replace(get_config("deepseek-7b"), num_layers=16,
                             attention_impl="flash_pallas")
    dlrm = dataclasses.replace(
        dlrm_paper.PAPER_COMPLEX,
        table_rows=dlrm_paper.powerlaw_rows(96, 8_000_000))
    return Sizes(lm=lm, slots=8, max_len=1024,
                 buckets=(32, 64, 128, 256, 512), requests=16,
                 prompt_len=(8, 512), new_tokens=32,
                 dlrm=dlrm, dlrm_batches=8, dlrm_batch=64)


def tiny_sizes() -> Sizes:
    """The same phases at toy widths, for a CPU rehearsal."""
    lm = dataclasses.replace(reduce_for_smoke(get_config("deepseek-7b")),
                             attention_impl="flash_pallas")
    dlrm = dlrm_paper.reduce_for_smoke(dlrm_paper.PAPER_COMPLEX)
    return Sizes(lm=lm, slots=4, max_len=64, buckets=(16, 32), requests=6,
                 prompt_len=(3, 32), new_tokens=4,
                 dlrm=dlrm, dlrm_batches=2, dlrm_batch=8)


# ---- bookkeeping -----------------------------------------------------------

class CompileClock:
    """Sums XLA backend-compile seconds while active (JAX's own
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


def tree_bytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def peak_bytes(device=None) -> str:
    peak = ((device or jax.devices()[0]).memory_stats() or {}).get(
        "peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def say(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---- LM --------------------------------------------------------------------

def init_lm(cfg: ModelConfig, seed: int = SEED):
    """Random weights made on the device inside one jit, so each leaf is
    cast to bf16 as it is drawn."""
    return jax.jit(functools.partial(model_mod.init_params, cfg))(
        jax.random.PRNGKey(seed))


def lm_requests(sz: Sizes, seed: int = SEED) -> List[Request]:
    rng = np.random.default_rng(seed)
    lo, hi = sz.prompt_len
    lens = rng.integers(lo, hi + 1, sz.requests)
    return [Request(i, rng.integers(0, sz.lm.vocab_size, n).astype(np.int32),
                    max_new_tokens=sz.new_tokens)
            for i, n in enumerate(lens)]


def engine_kw(sz: Sizes) -> dict:
    return dict(batch_slots=sz.slots, max_len=sz.max_len,
                prefill_buckets=sz.buckets)


def check_served(reqs: List[Request], sz: Sizes) -> None:
    short = [r.rid for r in reqs
             if not r.done or len(r.output) != sz.new_tokens]
    if short:
        raise SystemExit(f"FAIL: requests {short} did not finish with "
                         f"{sz.new_tokens} tokens")


def lm_phase(sz: Sizes, params) -> List[Request]:
    """Serve the trace through ``InferenceEngine.run``."""
    reqs = lm_requests(sz)
    with CompileClock() as cc:
        t0 = time.perf_counter()
        eng = InferenceEngine(sz.lm, params, **engine_kw(sz))
        eng.run(reqs)
        wall = time.perf_counter() - t0
    check_served(reqs, sz)
    tel = eng.telemetry
    say(f"lm: params {tree_bytes(params)} B, kv cache "
        f"{tree_bytes(eng.caches)} B, {sz.lm.num_layers} layers")
    say(f"lm: served {tel.served} requests, "
        f"{sum(len(r.output) for r in reqs)} tokens "
        f"({tel.prefills} prefills in {tel.prefill_batches} dispatches, "
        f"{tel.steps} decode steps) in {wall:.3f} s host wall, "
        f"compile included")
    say(f"lm: compile {cc.seconds:.3f} s over {cc.count} programs; "
        f"stages {dict(sorted(tel.compiles.items()))}")
    return reqs


@functools.partial(jax.jit, static_argnames=("cfg", "start"))
def _reference_logits(params, tokens, *, cfg: ModelConfig, start: int):
    """Plain forward over one whole sequence, no cache: float32 logits
    at positions ``start..``."""
    x, _, _ = model_mod.forward(params, cfg, {"tokens": tokens[None]},
                                mode="full")
    head = model_mod.head_table(params, cfg)
    logits = jnp.einsum("sd,vd->sv", x[0, start:], head,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
    return logits[:, :cfg.vocab_size]


def lm_reference_check(sz: Sizes, params, reqs: List[Request]) -> float:
    """Teacher-forced check of every generated token of the requests
    with the shortest and the longest prompt. Returns the worst logit
    gap (in reference-logit standard deviations)."""
    ref_cfg = dataclasses.replace(sz.lm, attention_impl="chunked_jnp")
    picks = sorted({min(reqs, key=lambda r: len(r.tokens)).rid,
                    max(reqs, key=lambda r: len(r.tokens)).rid})
    worst, mismatches, checked = 0.0, 0, 0
    with CompileClock() as cc:
        for rid in picks:
            r = reqs[rid]
            L = len(r.tokens)
            seq = np.concatenate([r.tokens, np.asarray(r.output[:-1],
                                                       np.int32)])
            logits = np.asarray(_reference_logits(
                params, jnp.asarray(seq), cfg=ref_cfg, start=L - 1))
            for k, tok in enumerate(r.output):
                row = logits[k]
                gap = float(row.max() - row[tok]) / float(row.std())
                worst = max(worst, gap)
                mismatches += int(tok != int(row.argmax()))
                checked += 1
                if gap > GAP_TOL:
                    raise SystemExit(
                        f"FAIL: request {rid} token {k}: engine chose {tok}, "
                        f"reference argmax {int(row.argmax())}, gap "
                        f"{gap:.4f} std > {GAP_TOL}")
    say(f"lm reference: requests {picks}, {checked} tokens checked, "
        f"{mismatches} argmax mismatches within tolerance, worst logit gap "
        f"{worst:.6f} std (tolerance {GAP_TOL}); compile "
        f"{cc.seconds:.3f} s over {cc.count} programs")
    return worst


# ---- DLRM ------------------------------------------------------------------

def dlrm_reference(params, cfg: DLRMConfig, asn, batch) -> np.ndarray:
    """float64 numpy forward: dequantized bag sums, bottom MLP, pairwise
    dot interactions, top MLP."""
    q = {k: np.asarray(v) for k, v in params["slab_q"].items()}
    off = np.asarray(asn.table_offset)
    B, T, L = batch["indices"].shape
    pooled = np.zeros((B, T, cfg.embed_dim))
    for t in range(T):
        idx = batch["indices"][:, t] + off[t]                  # (B, L)
        rows = (q["q8"][idx].astype(np.float64)
                * q["scale"][idx].astype(np.float64)[..., None]
                + q["bias"][idx].astype(np.float64)[..., None])
        live = np.arange(L)[None, :] < batch["lengths"][:, t:t + 1]
        pooled[:, t] = (rows * live[..., None]).sum(axis=1)

    def mlp(layers, x, last_linear):
        for i, l in enumerate(layers):
            x = x @ np.asarray(l["w"], np.float64) + np.asarray(l["b"])
            if not (last_linear and i == len(layers) - 1):
                x = np.maximum(x, 0.0)
        return x

    bot = mlp(params["bottom"], batch["dense"].astype(np.float64), False)
    cat = np.concatenate([bot[:, None], pooled], axis=1)
    iu, ju = np.triu_indices(T + 1, k=1)
    inter = np.einsum("bid,bjd->bij", cat, cat)[:, iu, ju]
    return mlp(params["top"], np.concatenate([bot, inter], axis=1), True)[:, 0]


def dlrm_phase(sz: Sizes) -> None:
    cfg = sz.dlrm
    # one chip holds every table: a single shard
    asn = dlrm_mod.make_assignment(cfg, 1)
    with CompileClock() as cc:
        params = jax.jit(functools.partial(
            dlrm_mod.init_dlrm, cfg, asn, quantize=True))(
                jax.random.PRNGKey(SEED))
        batches = [next(dlrm_batches(cfg, sz.dlrm_batch, seed=s))
                   for s in range(sz.dlrm_batches)]
        eng = DLRMEngine(cfg, asn, params)
        # warm-up, as launch/serve.py does: each stage, and the sparse
        # stage's width buckets, compile on the first batch
        eng.serve(batches, pipelined=True, warm=True)
        t0 = time.perf_counter()
        outs, stats = eng.serve(batches, pipelined=True)
        outs = [np.asarray(o) for o in outs]
        wall = time.perf_counter() - t0
    say(f"dlrm: {cfg.num_tables} tables, {sum(cfg.table_rows)} rows, "
        f"embed_dim {cfg.embed_dim}; params {tree_bytes(params)} B "
        f"(slab {tree_bytes(params['slab_q'])} B int8 rows + scales)")
    if len(outs) != len(batches):
        raise SystemExit(f"FAIL: {len(outs)} DLRM outputs for "
                         f"{len(batches)} batches")
    worst = 0.0
    for i, (o, b) in enumerate(zip(outs, batches)):
        if o.shape != (sz.dlrm_batch,) or not np.all(np.isfinite(o)):
            raise SystemExit(f"FAIL: DLRM batch {i}: output shape {o.shape}"
                             f" or non-finite values")
        ref = dlrm_reference(params, cfg, asn, b)
        err = float(np.max(np.abs(o - ref)) / max(np.max(np.abs(ref)),
                                                   1e-30))
        worst = max(worst, err)
        if err > DLRM_TOL:
            raise SystemExit(f"FAIL: DLRM batch {i}: error {err:.4g} of the "
                             f"largest |logit| > {DLRM_TOL}")
    say(f"dlrm: served {stats.num_requests} batches x{sz.dlrm_batch} in "
        f"{wall:.3f} s host wall after warm-up; worst error vs float64 "
        f"reference {worst:.6f} of max |logit| (tolerance {DLRM_TOL})")
    say(f"dlrm: compile {cc.seconds:.3f} s over {cc.count} programs; "
        f"stages {dict(sorted(eng.telemetry.compiles.items()))}")


# ---- four chips ------------------------------------------------------------

def placed_on(eng: InferenceEngine) -> set:
    return {d for leaf in jax.tree.leaves((eng.run_params, eng.caches))
            for d in leaf.devices()}


def four_chip_phase(sz: Sizes, n: int = 4) -> None:
    """n pinned replicas behind the router vs one replica on device 0,
    token for token. Both sides prefill one request per dispatch
    (``max_prefill_batch=1``), so each request runs the same programs
    whichever replica serves it and with whatever company."""
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(f"FAIL: --four-chips needs {n} devices, "
                         f"found {len(devs)}")
    kw = dict(engine_kw(sz), max_prefill_batch=1)
    with CompileClock() as cc:
        params = init_lm(sz.lm)
        t0 = time.perf_counter()
        one = InferenceEngine(sz.lm, params, device=devs[0], **kw)
        ref = lm_requests(sz)
        one.run(ref)
        one_wall = time.perf_counter() - t0
    check_served(ref, sz)
    del one
    gc.collect()
    say(f"one replica on {devs[0]}: {sum(len(r.output) for r in ref)} "
        f"tokens in {one_wall:.3f} s host wall; compile {cc.seconds:.3f} s "
        f"over {cc.count} programs")

    with CompileClock() as cc:
        t0 = time.perf_counter()
        router = ReplicaRouter(make_replicas(sz.lm, params, n,
                                             devices=devs[:n], **kw))
        reqs = lm_requests(sz)
        for r in reqs:
            router.submit(r)
        router.run_until_drained()
        fleet_wall = time.perf_counter() - t0
    check_served(reqs, sz)
    for i, eng in enumerate(router.replicas):
        where = placed_on(eng)
        say(f"replica {i}: device {devs[i]} ({devs[i].device_kind}), "
            f"routed {router.routed[i]}, served {eng.telemetry.served}, "
            f"peak {peak_bytes(devs[i])}")
        if where != {devs[i]}:
            raise SystemExit(f"FAIL: replica {i} arrays on {where}, "
                             f"not {devs[i]}")
    bad = [r.rid for r, m in zip(reqs, ref) if r.output != m.output]
    if bad:
        raise SystemExit(f"FAIL: fleet outputs differ from one replica "
                         f"for requests {bad}")
    say(f"fleet of {n}: {sum(len(r.output) for r in reqs)} tokens in "
        f"{fleet_wall:.3f} s host wall (replicas stepped in turn); compile "
        f"{cc.seconds:.3f} s over {cc.count} programs; all "
        f"{len(reqs)} requests token-identical to one replica")


# ---- entry point -----------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pinned four-replica phase and its "
                         "one-replica comparison")
    args = ap.parse_args(argv)

    dev = device_info()
    say(f"device: {dev['kind']} x{dev['count']} ({dev['platform']}); "
        f"jax {jax.__version__}; compile cache {compile_cache_dir()}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"FAIL: platform {dev['platform']!r} is not a TPU")
    configure_compile_cache()
    sz = full_sizes()
    t0 = time.perf_counter()

    if args.four_chips:
        four_chip_phase(sz)
    else:
        params = init_lm(sz.lm)
        reqs = lm_phase(sz, params)
        lm_reference_check(sz, params, reqs)
        say(f"lm: peak_bytes_in_use {peak_bytes()}")
        del params, reqs
        gc.collect()
        say(f"after lm: {sum(a.nbytes for a in jax.live_arrays())} B live")
        dlrm_phase(sz)
        say(f"dlrm: peak_bytes_in_use {peak_bytes()} (whole process)")

    say(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
