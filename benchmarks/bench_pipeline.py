"""Paper Fig. 6 (right), generalized: N-stage pipelined execution —
request N's dense compute overlaps request N+1's sparse lookups (and
request N+2's host ingest, now stage 0 of the same driver). MEASURED
end-to-end through the DLRM serving engine on CPU, against the analytic
steady-state bound sum(stages)/max(stage).
"""
from __future__ import annotations

from typing import List

import jax

from benchmarks.common import Row
from repro.configs import dlrm_paper
from repro.core.pipeline import steady_state_speedup
from repro.data.synthetic import dlrm_batches
from repro.models import dlrm as D
from repro.serving.dlrm_engine import DLRMEngine


def run() -> List[Row]:
    cfg = dlrm_paper.reduce_for_smoke(dlrm_paper.PAPER_COMPLEX)
    asn = D.make_assignment(cfg, 4)
    params = D.init_dlrm(cfg, asn, jax.random.PRNGKey(0))
    eng = DLRMEngine(cfg, asn, params)
    batches = [next(dlrm_batches(cfg, 64, seed=s)) for s in range(24)]
    # warm every stage (the sparse stage compiles its width buckets on
    # the first batch), so no compile leaks into the first measured pass
    eng.serve(batches, pipelined=True, warm=True)
    _, piped = eng.serve(batches, pipelined=True, warm=True, measure=True)
    _, seq = eng.serve(batches, pipelined=False, warm=True)
    speedup = seq.wall_time_s / max(piped.wall_time_s, 1e-9)
    bound = steady_state_speedup(*piped.stage_time_s.values())
    stage_csv = ";".join(f"{k}_s={v:.3f}"
                         for k, v in piped.stage_time_s.items())
    return [Row(
        f"pipeline/dlrm-{eng._pipeline.num_stages}-stage",
        piped.wall_time_s / piped.num_requests * 1e6,
        f"speedup={speedup:.2f}x;analytic_bound={bound:.2f}x;"
        f"qps_pipelined={piped.qps:.0f};qps_sequential={seq.qps:.0f};"
        f"{stage_csv};measured=true")]
