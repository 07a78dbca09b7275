"""Whole DLRM step (DLRMEngine.step_once): FLOPs per sample x items answered,
over the steps' host wall x peak bf16 FLOP/s. Steps before the traced
stretch only. Moves ``rank_p95_ms``."""
from bench import counts


def read(run):
    per = counts.dlrm_flops_per_sample(run.cfg)
    flops = wall = 0.0
    for st in run.win["steps"]:
        if st["t0"] >= run.win["untraced_until"]:
            continue
        flops += per * st["items"]
        wall += st["t1"] - st["t0"]
    if not wall:
        return None
    return 100 * flops / (wall * run.peaks["bf16_flops_per_s"])
