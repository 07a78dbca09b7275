"""Paper §VI-B: "we see a 2.6x speedup [in NLP] when parallelizing using
this heuristic compared to not doing so."

TPU analogue of splitting ops across Accel Cores = tensor-parallel sharding
over the 'model' mesh axis. We lower the XLM-R forward on 8 placeholder
devices twice — ops unsplit (every core computes the whole op) vs ops split
(heads/FFN sharded) — and compare the per-device roofline bound from the
compiled HLO. Structural measurement of real compiled artifacts; no
wall-clock TPU numbers in this container.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List

from benchmarks.common import Row


def _worker(tp: int, seq: int, batch: int) -> Dict[str, float]:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    # placeholder CPU devices only: the chip (if any) belongs to the parent
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks._parallelize_worker",
         str(tp), str(seq), str(batch)],
        capture_output=True, text=True, env=env, check=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return json.loads(out.stdout.strip().splitlines()[-1])


def _bound(t: Dict[str, float]) -> float:
    return max(t["compute_s"], t["memory_s"], t["collective_s"])


def run() -> List[Row]:
    rows: List[Row] = []
    for seq, batch in ((64, 1), (64, 8)):
        unsplit = _worker(1, seq, batch)
        split = _worker(8, seq, batch)
        speedup = _bound(unsplit) / max(_bound(split), 1e-12)
        rows.append(Row(
            f"parallelize/xlmr-seq{seq}-b{batch}", 0.0,
            f"tp8_speedup={speedup:.2f}x;paper_claim=2.6x;"
            f"unsplit_bound_us={_bound(unsplit)*1e6:.1f};"
            f"split_bound_us={_bound(split)*1e6:.1f};"
            f"split_collective_us={split['collective_s']*1e6:.1f};"
            f"source=compiled_hlo_roofline"))
    return rows
