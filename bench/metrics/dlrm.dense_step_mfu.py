"""Dense stage (models/dlrm.py: dense_forward, DCN V2): the least FLOPs of
the traced steps' items (``bench/counts_dcn.py``) over the device time of
the ``jit_dense_fn`` program in those steps x peak bf16 FLOP/s. Moves
``completed_rps``."""
from bench import counts_dcn

PROGRAM = r"^jit_dense_fn$"


def read(run):
    tr = run.trace
    if tr is None or not run.win["traced_steps"] \
            or "dcn_low_rank_dim" not in run.cfg:
        return None
    spans = sorted(tr.spans_named("bench.step_once"), key=lambda s: s.start)
    steps = [run.win["steps"][i] for i in run.win["traced_steps"]]
    flops = device = 0.0
    for s, st in zip(spans, steps):
        ops = tr.ops_within(s.start, s.end, PROGRAM, key="module")
        if not ops:
            continue
        device += tr.device_s(ops)
        flops += counts_dcn.dense_flops_per_item(run.cfg) * st["items"]
    if not device:
        return None
    return 100 * flops / (device * run.peaks["bf16_flops_per_s"])
