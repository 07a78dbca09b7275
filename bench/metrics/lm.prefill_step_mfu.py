"""Model step, prefill (the ``jit_prefill_step`` and ``jit_slot_write``
programs, or ``jit_chunk_prefill_step``): over the traced ticks that ran a
prefill, the FLOPs of the prompts prefilled (real lengths, no bucket or
batch padding; ``counts.prefill_flops``) over the device time of those
programs in those ticks x peak bf16 FLOP/s. Base: the prefill programs'
own device time, without the host round trip. Moves ``ttft_p95_ms``."""
from bench import counts
from bench.program_spans import PREFILL, device_s, program_ops, traced_ticks


def read(run):
    dm = run.result["dims"]
    flops = device = 0.0
    for span, t in traced_ticks(run):
        if not t["prefill"]:
            continue
        ops = program_ops(run.trace, span, PREFILL)
        if not ops:
            continue
        device += device_s(run.trace, ops)
        flops += sum(counts.prefill_flops(dm, n) for n in t["prompts"])
    if not device:
        return None
    return 100 * flops / (device * run.peaks["bf16_flops_per_s"])
