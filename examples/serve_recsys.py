"""End-to-end driver — the paper's centerpiece: serve a recommendation
model through the full accelerator pipeline (Fig. 2 + Fig. 6).

  click-log ingestion (partial tensor transfers + command batching, T6)
    -> sparse stage: SLS over tables partitioned across shards with
       length-aware load balancing (T1/T8)
    -> dense stage: bottom MLP + interaction + top MLP, data-parallel
  with request N's dense compute overlapping request N+1's sparse lookups
  (T2), int8 row-wise quantized embedding tables (T3), and an NE
  accuracy check against the fp32 reference (SecV).

Run: PYTHONPATH=src python examples/serve_recsys.py [--batches 32]
     [--batch-size 64] [--no-quant] [--full-config]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import dlrm_paper
from repro.core.metrics import ne_delta, normalized_entropy
from repro.core.partitioner import balance_report
from repro.data.synthetic import dlrm_batches
from repro.models import dlrm as D
from repro.serving.dlrm_engine import DLRMEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--shards", type=int, default=6,
                    help="six accelerator cards, as deployed (SecIII)")
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full-config", dest="smoke", action="store_false")
    args = ap.parse_args(argv)

    cfg = dlrm_paper.PAPER_COMPLEX
    if args.smoke:
        cfg = dlrm_paper.reduce_for_smoke(cfg)

    # T1/T8: partition tables across shards, length-aware
    rep = balance_report(cfg.table_rows, cfg.avg_lookups_per_table,
                         args.shards, cfg.embed_dim)
    asn = D.make_assignment(cfg, args.shards, length_aware=True)
    print(f"model {cfg.name}: {cfg.num_tables} tables, "
          f"{cfg.embedding_params():,} embed params, "
          f"{cfg.dense_params():,} dense params")
    print(f"partitioned over {args.shards} shards: imbalance "
          f"{asn.imbalance:.2f} (naive {rep['naive_imbalance']:.2f}; "
          f"SLS latency saved {rep['latency_reduction']*100:.0f}%)")

    # T3: int8 row-wise quantized tables (fp32 reference kept for NE check)
    key = jax.random.PRNGKey(0)
    params_ref = D.init_dlrm(cfg, asn, key, quantize=False)
    params = params_ref if args.no_quant else \
        D.init_dlrm(cfg, asn, key, quantize=True)
    eng = DLRMEngine(cfg, asn, params)

    batches = [next(dlrm_batches(cfg, args.batch_size, seed=s))
               for s in range(args.batches)]
    # warm-up (each stage, and the sparse stage's width buckets, compile
    # on the first batch); excluded from transfer/latency stats
    eng.serve(batches, pipelined=True, warm=True)

    outs, stats = eng.serve(batches, pipelined=True)
    print(f"\nserved {stats.num_requests} request batches "
          f"x{args.batch_size} in {stats.wall_time_s*1e3:.0f} ms through "
          f"the {eng._pipeline.num_stages}-stage pipeline "
          f"({'>'.join(eng._pipeline.stage_names)}; "
          f"{stats.qps * args.batch_size:.0f} items/s)")
    print(f"T6 partial transfers: shipped "
          f"{eng.transfer_stats.bytes_partial/1e6:.2f} MB of "
          f"{eng.transfer_stats.bytes_full/1e6:.2f} MB "
          f"({eng.transfer_stats.bytes_saved_frac*100:.0f}% saved), "
          f"{eng.transfer_stats.num_transfers_batched} transfers instead of "
          f"{eng.transfer_stats.num_transfers_naive}")

    _, piped = eng.serve(batches, pipelined=True, warm=True, measure=True)
    from repro.core.pipeline import steady_state_speedup
    bound = steady_state_speedup(*piped.stage_time_s.values())
    _, seq_stats = eng.serve(batches, pipelined=False, warm=True)
    per_stage = " ".join(f"{k}={v*1e3:.0f}ms"
                         for k, v in piped.stage_time_s.items())
    print(f"T2 pipelining: measured "
          f"{seq_stats.wall_time_s/max(piped.wall_time_s,1e-9):.2f}x vs "
          f"sequential; steady-state bound {bound:.2f}x ({per_stage}). "
          f"On one CPU device all stages share cores; the bound is "
          f"realized on disjoint sparse/dense shards (paper Fig. 6).")
    print(eng.telemetry.report())

    # SecV: accuracy — NE delta of the quantized model vs fp32 reference
    b = {k: jnp.asarray(v) for k, v in batches[0].items()}
    ref_logits = D.dlrm_forward(params_ref, cfg, asn, b["dense"],
                                b["indices"], b["lengths"])
    logits = np.asarray(outs[0])
    d = ne_delta(jnp.asarray(logits), ref_logits, b["labels"])
    ne = float(normalized_entropy(ref_logits, b["labels"]))
    print(f"SecV accuracy: NE={ne:.4f}, quantized NE delta {d:+.2e} "
          f"(paper budget 5e-4): {'OK' if abs(d) < 5e-4 else 'OVER'}")


if __name__ == "__main__":
    main()
