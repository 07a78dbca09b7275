"""Model step, prefill: useful FLOPs of the ticks that ran a prefill (the
prompt tokens prefilled, without bucket or batch padding, plus that tick's
decoded tokens) over those ticks' host wall x peak bf16 FLOP/s x chips.
A tick ran a prefill when ``telemetry.prefill_batches`` grew. Ticks before
the traced stretch only. Moves ``ttft_p95_ms``."""
from bench import counts


def read(run):
    dm = run.result["dims"]
    flops = wall = 0.0
    for t in run.win["ticks"]:
        if not t["prefill"] or t["t0"] >= run.win["untraced_until"]:
            continue
        flops += sum(counts.prefill_flops(dm, n) for n in t["prompts"])
        flops += sum(counts.decode_flops(dm, c) for c in t["contexts"])
        wall += t["t1"] - t["t0"]
    if not wall:
        return None
    return 100 * flops / (wall * run.peaks["bf16_flops_per_s"]
                          * run.cell["chips"])
