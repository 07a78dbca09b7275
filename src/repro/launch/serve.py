"""Serving launcher: ``python -m repro.launch.serve --arch deepseek-7b
--requests 32`` — continuous-batching LM serving with bucketed batched
prefill (paper T5) through the unified runtime, or ``--arch dlrm`` for the
paper's 4-stage pipelined recommendation engine (ingest→sparse→dense→post).

Both paths share the scheduler/executor/telemetry stack
(repro/serving/): pick an admission policy with ``--policy
fifo|edf|sizetime|priority`` and a latency SLA with ``--slo-ms`` to get
SLA-miss accounting in the report. ``--replicas N`` fronts N engine
replicas with the ReplicaRouter (the paper's six-cards-behind-one-host
deployment): tickets route by queue depth + deadline slack
(``--route feedback`` switches to EWMA-of-dispatch-time costing for
heterogeneous fleets) and the report is the fleet-level telemetry
aggregate. ``--steal`` turns on cross-replica work stealing (idle
replicas pull pending fresh tickets from backlogged siblings;
``--verify-steal`` is the CI smoke: hot-spot everything onto replica 0,
kill it mid-run, assert nonzero steals and zero lost requests). ``--max-queue`` / ``--service-ms-est`` turn on bounded-queue /
deadline-feasibility admission control (shed requests are counted
separately from misses; pass ``--service-ms-est auto`` to calibrate the
estimate from live telemetry). ``--prefill-chunk N`` splits long prompts
into N-token chunks interleaved with decode steps (LM only) — the
head-of-line-blocking fix, for EVERY block pattern (global, local-ring,
SSM, RG-LRU, hybrids — the SequenceStateManager carries per-slot state
across chunk boundaries, PR 5); ``--verify-chunked`` replays the same
trace monolithically and asserts token-identical outputs (the CI smoke
runs it on deepseek-7b and on the recurrentgemma-9b stateful hybrid).
``--prefix-cache N`` (PR 8) turns on the content-hash prefix cache over
the same chunk machinery: prompt prefixes are snapshotted at chunk
granularity and a later request sharing the prefix is admitted with its
prefill already restored (``--verify-prefix`` is the CI smoke: replay a
hot-system-prompt trace through the warm cache and assert nonzero hits
with outputs token-identical to a cold engine).
``--precision w8a8`` (PR 6) runs the calibrated int8 serving path
(``--verify-quant`` replays the trace on fp32 and asserts the greedy-
token-agreement guardrail); ``--replica-precisions fp32,w8a8`` deploys a
heterogeneous fleet where the router pins class-0 traffic to fp32
replicas (``--verify-quant`` then asserts the pin held with zero lost —
the CI quant smoke). Reports include time-to-first-token percentiles
alongside latency.

Real-cluster notes: per-host processes share the production mesh via
jax.distributed.initialize(); the engine's slot batch maps to the
data-parallel axis and the ReplicaRouter plays the Glow runtime's
front-end balancer role (SecIV-C) across the per-card runtimes.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduce_for_smoke
from repro.core.backend import configure_compile_cache
from repro.models import model as model_mod
from repro.serving.engine import InferenceEngine, Request, make_replicas
from repro.serving.router import ReplicaRouter

# minimum greedy-token agreement of a w8a8 engine with fp32 on the same
# trace (--verify-quant)
QUANT_AGREEMENT_THRESHOLD = 0.90

# the hand-set chunk the CI chunked smoke uses — the reference the
# autotune smoke must be token-identical to
AUTOTUNE_REF_CHUNK = 16


def _lm_requests(args, cfg):
    rng = np.random.default_rng(7)
    lens = np.clip(rng.lognormal(3.0, 0.7, args.requests).astype(int), 3,
                   args.max_len // 2)
    # with the priority policy, tag ~1/4 of traffic latency-critical
    # (class 0) and the rest batch (class 1) — the paper's mixed traffic
    prios = (rng.integers(0, 4, args.requests) == 0).astype(int) ^ 1 \
        if args.policy == "priority" else np.zeros(args.requests, int)
    return [Request(i, rng.integers(0, cfg.vocab_size, l).astype(np.int32),
                    max_new_tokens=args.new_tokens, priority=int(p))
            for i, (l, p) in enumerate(zip(lens, prios))]


def serve_lm(args):
    cfg = reduce_for_smoke(get_config(args.arch)) if args.smoke \
        else get_config(args.arch)
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(batch_slots=args.slots, max_len=args.max_len,
              prefill_buckets=(16, 32, 64, 128), policy=args.policy,
              slo_ms=args.slo_ms, max_queue=args.max_queue,
              service_ms_est=args.service_ms_est,
              prefill_chunk=args.prefill_chunk,
              prefix_cache=args.prefix_cache)
    if args.verify_prefix:
        if args.replicas > 1:
            raise SystemExit("--verify-prefix runs single-engine only "
                             "(drop --replicas)")
        return _verify_prefix(args, cfg, params, kw)
    if args.verify_fleet_prefix:
        return _verify_fleet_prefix(args, cfg, params, kw)
    reqs = _lm_requests(args, cfg)
    if args.replicas > 1:
        if args.verify_chunked:
            raise SystemExit("--verify-chunked runs single-engine only "
                             "(drop --replicas)")
        if args.verify_autotune:
            raise SystemExit("--verify-autotune runs single-engine only "
                             "(drop --replicas)")
        precisions = [p.strip() for p in args.replica_precisions.split(",")] \
            if args.replica_precisions \
            else [args.precision] * args.replicas
        router = ReplicaRouter(make_replicas(cfg, params, args.replicas,
                                             precisions=precisions, **kw),
                               route=args.route, steal=args.steal)
        if args.verify_steal:
            return _verify_steal(router, reqs, args)
        if args.verify_quant:
            return _verify_quant_fleet(router, reqs, args)
        t0 = time.perf_counter()
        for r in reqs:
            router.submit(r)
        router.run_until_drained()
        tel = router.fleet_telemetry()
        wall = time.perf_counter() - t0
        print(f"fleet served {tel.served} requests in {wall:.2f}s "
              f"across {args.replicas} replicas "
              f"(routed {router.routed}, shed {router.shed})")
        print(router.report())
        return tel
    if args.verify_steal:
        raise SystemExit("--verify-steal needs --replicas >= 2 --steal")
    if args.replica_precisions:
        raise SystemExit("--replica-precisions needs --replicas >= 2")
    if args.verify_autotune:
        if args.prefill_chunk != "auto":
            raise SystemExit("--verify-autotune needs --prefill-chunk auto")
        from repro.serving.perf_model import PerfModel
        kw["perf_model"] = PerfModel()
    eng = InferenceEngine(cfg, params, precision=args.precision, **kw)
    t0 = time.perf_counter()
    eng.run(reqs)
    wall = time.perf_counter() - t0
    tel = eng.telemetry
    chunked = (f", {tel.continuations} chunk continuations"
               if args.prefill_chunk else "")
    print(f"served {tel.served} requests in {wall:.2f}s "
          f"({tel.total_tokens / wall:.0f} tok/s, {tel.steps} decode steps, "
          f"{tel.prefills} prefills in {tel.prefill_batches} batched "
          f"dispatches{chunked})")
    print(tel.report())
    if args.verify_chunked:
        if not args.prefill_chunk:
            raise SystemExit("--verify-chunked needs --prefill-chunk")
        ref_kw = dict(kw, prefill_chunk=None)
        ref = InferenceEngine(cfg, params, **ref_kw)
        ref_reqs = _lm_requests(args, cfg)
        ref.run(ref_reqs)
        bad = [r.rid for r, m in zip(reqs, ref_reqs) if r.output != m.output]
        if bad:
            raise SystemExit(f"FAIL: chunked outputs diverge from "
                             f"monolithic for requests {bad}")
        print(f"verify-chunked OK: {len(reqs)} requests token-identical "
              f"to monolithic prefill")
    if args.verify_autotune:
        chosen = eng.prefill_chunk
        if chosen not in eng.buckets:
            raise SystemExit(f"FAIL: auto chunk {chosen} is not on the "
                             f"bucket ladder {eng.buckets}")
        ref = InferenceEngine(cfg, params, precision=args.precision,
                              **dict(kw, perf_model=None,
                                     prefill_chunk=AUTOTUNE_REF_CHUNK))
        ref_reqs = _lm_requests(args, cfg)
        ref.run(ref_reqs)
        bad = [r.rid for r, m in zip(reqs, ref_reqs)
               if r.output != m.output]
        if bad:
            raise SystemExit(f"FAIL: auto-chunk outputs diverge from the "
                             f"hand-set chunk {AUTOTUNE_REF_CHUNK} for "
                             f"requests {bad}")
        print(f"verify-autotune OK: auto chunk {chosen} on ladder "
              f"{eng.buckets}; {len(reqs)} requests "
              f"token-identical to hand-set chunk {AUTOTUNE_REF_CHUNK}")
    if args.verify_quant:
        if args.precision != "w8a8":
            raise SystemExit("--verify-quant needs --precision w8a8 "
                             "(or a mixed --replica-precisions fleet)")
        from repro.core.metrics import token_agreement
        ref = InferenceEngine(cfg, params, precision="fp32", **kw)
        ref_reqs = _lm_requests(args, cfg)
        ref.run(ref_reqs)
        agreement = token_agreement([(r.output, m.output)
                                     for r, m in zip(reqs, ref_reqs)])
        if agreement < QUANT_AGREEMENT_THRESHOLD:
            raise SystemExit(
                f"FAIL: w8a8 greedy-token agreement {agreement:.3f} below "
                f"the {QUANT_AGREEMENT_THRESHOLD} guardrail")
        q = eng.quant
        print(f"verify-quant OK: {len(reqs)} requests, token agreement "
              f"{agreement:.3f} >= {QUANT_AGREEMENT_THRESHOLD} vs fp32 "
              f"({q.quantized_sites} sites int8, {q.fallback_sites} "
              f"fp32 fallbacks, calib disagreement "
              f"{q.result.metric_delta:.4f})")
    return tel


def _prefix_requests(args, cfg):
    """Hot-system-prompt trace: every request opens with the same
    3-chunk system prefix and ends with a short per-request suffix —
    the workload the prefix cache exists for."""
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, cfg.vocab_size,
                          3 * args.prefill_chunk).astype(np.int32)
    reqs = []
    for i in range(args.requests):
        tail = rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, 12))).astype(np.int32)
        reqs.append(Request(i, np.concatenate([prefix, tail]),
                            max_new_tokens=args.new_tokens))
    return reqs


def _verify_prefix(args, cfg, params, kw):
    """The CI prefix-cache smoke: run a hot-system-prompt trace once to
    populate the cache, replay it through the warm cache, and assert
    nonzero prefix hits with every replayed output token-identical to a
    cold engine (no cache) serving the same trace. Exits non-zero on
    any violation."""
    if not args.prefix_cache:
        raise SystemExit("--verify-prefix needs --prefix-cache")
    if not args.prefill_chunk:
        raise SystemExit("--prefix-cache needs --prefill-chunk")
    warm = InferenceEngine(cfg, params, precision=args.precision, **kw)
    warm.run(_prefix_requests(args, cfg))       # populate pass
    warm.telemetry.reset_serving_stats()
    hot = _prefix_requests(args, cfg)
    warm.run(hot)                               # replay: every prefix hits
    tel = warm.telemetry
    cold = InferenceEngine(cfg, params, precision=args.precision,
                           **dict(kw, prefix_cache=None))
    ref = _prefix_requests(args, cfg)
    cold.run(ref)
    bad = [r.rid for r, m in zip(hot, ref) if r.output != m.output]
    if bad:
        raise SystemExit(f"FAIL: cache-hit outputs diverge from cold "
                         f"prefill for requests {bad}")
    if tel.prefix_hits == 0:
        raise SystemExit("FAIL: no prefix hits on a replayed "
                         "hot-system-prompt trace")
    print(f"verify-prefix OK: {len(hot)} requests replayed, "
          f"{tel.prefix_hits} prefix-cache hits, outputs token-identical "
          f"to cold prefill")
    print(tel.report())
    return tel


def _verify_fleet_prefix(args, cfg, params, kw):
    """The CI fleet-prefix smoke (PR 10): a multi-replica fleet with the
    fleet-shared prefix tier under a hot-system-prompt trace. A populate
    pass lands the shared prefix on whichever replica serves it first;
    the rest of the trace then routes through the locality-aware
    steering path, which must produce nonzero remote hits (steered or
    shipped), lose nothing, and stay token-identical to a cold
    single-engine replay. Exits non-zero on any violation."""
    if args.replicas < 2:
        raise SystemExit("--verify-fleet-prefix needs --replicas >= 2")
    if not args.prefix_cache:
        raise SystemExit("--verify-fleet-prefix needs --prefix-cache")
    if not args.prefill_chunk:
        raise SystemExit("--prefix-cache needs --prefill-chunk")
    from repro.serving.perf_model import PerfModel
    pm = PerfModel.for_params(params)
    reqs = _prefix_requests(args, cfg)
    router = ReplicaRouter(make_replicas(cfg, params, args.replicas,
                                         **kw),
                           route=args.route, steal=args.steal,
                           perf_model=pm, fleet_prefix=True,
                           prefix_host_entries=4 * args.prefix_cache)
    # populate pass: the first request warms ONE replica's cache and
    # registers it in the fleet index — steering only has holders to
    # steer to once the index is populated
    router.submit(reqs[0])
    router.run_until_drained()
    for r in reqs[1:]:
        router.submit(r)
    router.run_until_drained()
    tel = router.fleet_telemetry()
    lost = [r.rid for r in reqs if not r.done]
    if lost:
        raise SystemExit(f"FAIL: fleet-prefix run lost requests {lost}")
    cold = InferenceEngine(cfg, params, precision=args.precision,
                           **dict(kw, prefix_cache=None))
    ref = _prefix_requests(args, cfg)
    cold.run(ref)
    bad = [r.rid for r, m in zip(reqs, ref) if r.output != m.output]
    if bad:
        raise SystemExit(f"FAIL: fleet-prefix outputs diverge from cold "
                         f"prefill for requests {bad}")
    if tel.prefix_remote_hits == 0:
        raise SystemExit("FAIL: no remote prefix hits on a hot-system-"
                         "prompt trace across the fleet")
    if tel.prefix_hits == 0:
        raise SystemExit("FAIL: no prefix-cache hits after steering")
    print(f"verify-fleet-prefix OK: {len(reqs)} requests across "
          f"{args.replicas} replicas (routed {router.routed}), "
          f"{tel.prefix_remote_hits} remote hits "
          f"({tel.prefix_shipped} shipped, {tel.prefix_recomputed} "
          f"priced-out recomputes, {tel.prefix_host_hits} host-tier "
          f"fault-ins), {tel.prefix_hits} local hits, 0 lost, outputs "
          f"token-identical to cold prefill")
    print(router.report())
    return tel


def _verify_quant_fleet(router, reqs, args):
    """The CI mixed-precision smoke: a 1xfp32 + 1xw8a8 fleet under the
    priority policy must route every latency/accuracy-critical (class-0)
    request to the fp32 replica while fp32 capacity exists, lose nothing,
    and count zero precision downgrades. Exits non-zero on any
    violation."""
    if not router.mixed_precision:
        raise SystemExit("--verify-quant with --replicas needs a mixed "
                         "--replica-precisions fleet (e.g. fp32,w8a8)")
    if not any(r.priority == 0 for r in reqs):
        raise SystemExit("FAIL: trace has no class-0 requests — the pin "
                         "check would be vacuous (use --policy priority "
                         "and enough --requests)")
    misrouted = []
    for r in reqs:
        before = list(router.routed)
        router.submit(r)
        j = next(i for i in range(len(router.replicas))
                 if router.routed[i] != before[i])
        if r.priority == 0 and router.precisions[j] != "fp32":
            misrouted.append(r.rid)
    router.run_until_drained()
    tel = router.fleet_telemetry()
    lost = [r.rid for r in reqs if not r.done]
    if lost:
        raise SystemExit(f"FAIL: mixed-precision fleet lost requests "
                         f"{lost}")
    if misrouted:
        raise SystemExit(f"FAIL: class-0 requests {misrouted} routed to "
                         f"an int8 replica while fp32 was live")
    if tel.precision_rehomed:
        raise SystemExit(f"FAIL: {tel.precision_rehomed} precision "
                         f"downgrades counted with fp32 live throughout")
    high = sum(r.priority == 0 for r in reqs)
    print(f"verify-quant OK: mixed fleet {router.precisions} served "
          f"{tel.served} requests (routed {router.routed}), all {high} "
          f"class-0 on fp32, 0 lost, 0 downgrades")
    print(router.report())
    return tel


def _verify_steal(router, reqs, args):
    """The CI steal smoke: a hot-keyed stream lands every request on
    replica 0, so only stealing puts the siblings to work; replica 0 is
    then killed mid-run and its outstanding load must drain to the
    survivors with zero lost requests. Exits non-zero on any violation."""
    if not args.steal:
        raise SystemExit("--verify-steal needs --steal")
    for r in reqs:
        router.replicas[0].submit(r)        # hot spot: bypass the balancer
    rounds = 0
    while router.has_work:
        router.maybe_steal()
        for i, rep in enumerate(router.replicas):
            if not router.dead[i] and rep.has_work:
                rep.step_once()
        rounds += 1
        if rounds == 2:
            router.drain_replica(0)         # the card dies mid-run
    tel = router.fleet_telemetry()
    lost = [r.rid for r in reqs if not r.done]
    if lost:
        raise SystemExit(f"FAIL: fault drain lost requests {lost}")
    if tel.steals == 0:
        raise SystemExit("FAIL: no steals under a hot-keyed stream")
    if tel.drained == 0:
        raise SystemExit("FAIL: mid-run kill drained nothing")
    print(f"verify-steal OK: {len(reqs)} requests, {tel.steals} stolen, "
          f"{tel.drained} re-homed by the kill, 0 lost")
    print(router.report())
    return tel


def elastic_smoke():
    """The CI elastic smoke (PR 7): the flash-crowd scenario on the
    deterministic fleet sim with a FleetController in the loop and a
    card frozen mid-crowd. Asserts the full control surface — scale-up
    under the crowd, scale-down through the trough, exactly one
    missed-heartbeat fault drain, zero lost tickets, and both headline
    wins vs the fixed fleet (less shedding at the peak, fewer
    replica-seconds burned). Exits non-zero on any violation. Runs on
    the virtual clock: no model, no compiles, bit-deterministic."""
    from repro.serving.fleet_sim import elastic_vs_fixed
    r = elastic_vs_fixed(kill_at_frac=0.33)
    ctl, el = r["controller"], r["elastic"]
    checks = [
        (ctl.scale_ups >= 1, "no scale-up under the flash crowd"),
        (ctl.scale_downs >= 1, "no scale-down through the trough"),
        (ctl.faults_drained == 1,
         f"expected exactly 1 fault drain, got {ctl.faults_drained}"),
        (r["zero_lost"], f"lost tickets: elastic {el['lost']}, "
                         f"fixed {r['fixed']['lost']}"),
        (r["shed_improved"], f"elastic shed {el['shed']} not below "
                             f"fixed {r['fixed']['shed']}"),
        (r["capacity_improved"],
         f"elastic burned {r['replica_seconds_elastic']:.1f} replica-s "
         f"vs fixed {r['replica_seconds_fixed']:.1f}"),
    ]
    bad = [msg for ok, msg in checks if not ok]
    if bad:
        raise SystemExit("FAIL: elastic smoke: " + "; ".join(bad))
    print(f"elastic-smoke OK: {el['completed']} served, shed "
          f"{el['shed']} (fixed {r['fixed']['shed']}), "
          f"{r['replica_seconds_elastic']:.1f} replica-s "
          f"(fixed {r['replica_seconds_fixed']:.1f}), +{ctl.scale_ups} "
          f"up / -{ctl.scale_downs} down / {ctl.faults_drained} fault "
          f"drain, 0 lost")
    print(ctl.report())
    return ctl


def serve_dlrm(args):
    from repro.configs import dlrm_paper
    from repro.data.synthetic import dlrm_batches
    from repro.models import dlrm as dlrm_mod
    from repro.serving.dlrm_engine import DLRMEngine
    from repro.serving.dlrm_engine import make_replicas as dlrm_replicas
    cfg = dlrm_paper.reduce_for_smoke(dlrm_paper.PAPER_COMPLEX) if args.smoke \
        else dlrm_paper.PAPER_COMPLEX
    asn = dlrm_mod.make_assignment(cfg, 6)
    params = dlrm_mod.init_dlrm(cfg, asn, jax.random.PRNGKey(0),
                                quantize=True)
    kw = dict(policy=args.policy, slo_ms=args.slo_ms,
              max_queue=args.max_queue, service_ms_est=args.service_ms_est)
    batches = [next(dlrm_batches(cfg, 64, seed=s))
               for s in range(args.requests)]
    if args.replicas > 1:
        router = ReplicaRouter(dlrm_replicas(cfg, asn, params,
                                             args.replicas, **kw),
                               route=args.route, steal=args.steal)
        # warm-up per replica (each stage, and the sparse stage's width
        # buckets, compile on its first batch), excluded from
        # latency/transfer stats
        for rep in router.replicas:
            rep.serve(batches, pipelined=True, warm=True)
            rep.telemetry.reset_serving_stats()
        for b in batches:
            router.submit(b)
        router.run_until_drained()
        tel = router.fleet_telemetry()
        print(f"fleet served {tel.served} batches x64 across "
              f"{args.replicas} replicas (routed {router.routed}, "
              f"shed {router.shed})")
        print(router.report())
        return tel
    eng = DLRMEngine(cfg, asn, params, **kw)
    # warm-up: each stage, and the sparse stage's width buckets, compile
    # on the first batch; excluded from transfer + latency stats
    eng.serve(batches, pipelined=True, warm=True)
    _, stats = eng.serve(batches, pipelined=True)
    tel = eng.telemetry
    print(f"served {stats.num_requests} batches x64 "
          f"({stats.qps * 64:.0f} items/s device-side); "
          f"transfers saved {eng.transfer_stats.bytes_saved_frac*100:.0f}% "
          f"bytes")
    print(tel.report())
    return tel


def _service_est(v: str):
    return v if v == "auto" else float(v)


def _chunk_arg(v: str):
    return v if v == "auto" else int(v)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--policy", default="fifo",
                    choices=("fifo", "edf", "sizetime", "priority"))
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request latency SLA for EDF + miss accounting")
    ap.add_argument("--replicas", type=int, default=1,
                    help="front N engine replicas with the ReplicaRouter")
    ap.add_argument("--route", default="count",
                    choices=("count", "feedback"),
                    help="router cost: ticket counts or EWMA of measured "
                         "per-replica dispatch time")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded queue: shed submits past this depth")
    ap.add_argument("--service-ms-est", type=_service_est, default=None,
                    help="per-ticket service estimate for deadline-"
                         "feasibility shedding (a number, or 'auto' to "
                         "calibrate from live telemetry)")
    ap.add_argument("--steal", action="store_true",
                    help="cross-replica work stealing: idle replicas pull "
                         "pending fresh tickets from backlogged siblings")
    ap.add_argument("--verify-steal", action="store_true",
                    help="hot-spot all requests onto replica 0, kill it "
                         "mid-run, and assert nonzero steals + zero lost "
                         "requests (the CI steal smoke)")
    ap.add_argument("--prefill-chunk", type=_chunk_arg, default=None,
                    help="split prompts into N-token chunks interleaved "
                         "with decode steps (LM only); 'auto' picks the "
                         "chunk at the perf model's per-bucket "
                         "efficiency knee")
    ap.add_argument("--verify-chunked", action="store_true",
                    help="replay the trace monolithically and assert "
                         "chunked outputs are token-identical")
    ap.add_argument("--verify-autotune", action="store_true",
                    help="with --prefill-chunk auto: assert the chosen "
                         "chunk is on the bucket ladder and "
                         "token-identical to the hand-set default "
                         "(the CI autotune smoke)")
    ap.add_argument("--prefix-cache", type=int, default=None,
                    help="content-hash prefix cache capacity (entries): "
                         "snapshot prompt prefixes at chunk granularity "
                         "and admit later shared-prefix requests with "
                         "prefill already restored (needs "
                         "--prefill-chunk)")
    ap.add_argument("--verify-prefix", action="store_true",
                    help="replay a hot-system-prompt trace through the "
                         "warm prefix cache and assert nonzero hits with "
                         "outputs token-identical to a cold engine (the "
                         "CI prefix smoke)")
    ap.add_argument("--verify-fleet-prefix", action="store_true",
                    help="multi-replica fleet with the fleet-shared "
                         "prefix tier under a hot-system-prompt trace: "
                         "assert nonzero remote hits, zero lost, and "
                         "outputs token-identical to cold prefill (the "
                         "CI fleet-prefix smoke; needs --replicas >= 2)")
    ap.add_argument("--precision", default="fp32",
                    choices=("fp32", "w8a8"),
                    help="engine execution precision: w8a8 runs every "
                         "calibrated dense projection as per-channel int8 "
                         "weights x dynamically scaled int8 activations")
    ap.add_argument("--replica-precisions", default=None,
                    help="comma list, one per replica (e.g. fp32,w8a8): "
                         "heterogeneous fleet where the router pins "
                         "class-0 traffic to fp32 replicas")
    ap.add_argument("--verify-quant", action="store_true",
                    help="single engine: replay the trace on fp32 and "
                         "assert the w8a8 token-agreement guardrail; "
                         "mixed fleet: assert class-0 routes to fp32 with "
                         "zero lost (the CI quant smoke)")
    ap.add_argument("--elastic-smoke", action="store_true",
                    help="run the elastic fleet-controller scenario on "
                         "the deterministic fleet sim (flash crowd + "
                         "mid-crowd card freeze) and assert scale-up/"
                         "scale-down/fault-drain with zero lost — the "
                         "CI elastic smoke; ignores the engine flags")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full-config", dest="smoke", action="store_false")
    args = ap.parse_args(argv)
    configure_compile_cache()
    if args.elastic_smoke:
        return elastic_smoke()
    if args.arch == "dlrm":
        return serve_dlrm(args)
    return serve_lm(args)


if __name__ == "__main__":
    main()
