"""Model step, decode: FLOPs of the tokens decoded in ticks that ran only a
decode step, over those ticks' host wall x peak bf16 FLOP/s x chips. The
whole decode step's share of the chip's compute peak, beside its memory
roofline. Ticks before the traced stretch only. Moves ``itl_p95_ms``."""
from bench import counts


def read(run):
    dm = run.result["dims"]
    flops = wall = 0.0
    for t in run.win["ticks"]:
        if t["prefill"] or not t["decode_steps"] \
                or t["t0"] >= run.win["untraced_until"]:
            continue
        flops += sum(counts.decode_flops(dm, c) for c in t["contexts"])
        wall += t["t1"] - t["t0"]
    if not wall:
        return None
    return 100 * flops / (wall * run.peaks["bf16_flops_per_s"]
                          * run.cell["chips"])
