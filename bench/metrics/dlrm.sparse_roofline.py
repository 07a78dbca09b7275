"""Sparse stage (models/dlrm.py: sls_forward): the least bytes of the traced
steps' lookups (each live lookup reads one int8 row with its fp16 scale and
bias; the pooled float32 output is written once) over the device time of
the ``sparse_fn`` program in those steps x HBM bytes/s. Moves
``rank_p95_ms``."""
from bench import counts

PROGRAM = r"sparse_fn"


def read(run):
    tr = run.trace
    if tr is None or not run.win["traced_steps"]:
        return None
    spans = sorted(tr.spans_named("bench.step_once"), key=lambda s: s.start)
    steps = [run.win["steps"][i] for i in run.win["traced_steps"]]
    nbytes = device = 0.0
    for s, st in zip(spans, steps):
        ops = tr.ops_within(s.start, s.end, PROGRAM, key="module")
        if not ops:
            continue
        device += tr.device_s(ops)
        nbytes += counts.sparse_least_bytes(run.cfg, st["lookups"],
                                            st["items"])
    if not device:
        return None
    return 100 * nbytes / (device * run.peaks["hbm_bytes_per_s"])
