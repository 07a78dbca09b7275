"""Kernel (kernels/flash_attn): the least time of the flash-attention calls
in the traced ticks that ran a prefill (per call the larger of its FLOPs
over peak and its bytes over HBM bandwidth, from the real prompt lengths of
each bucket group, once per layer) over the device time of the kernel's
events in those ticks. Moves ``ttft_p95_ms``."""
from collections import defaultdict

from bench import counts

KERNEL = r"^flash_attn"


def groups(prompts, buckets):
    """Prompt lengths by the bucket the engine prefills them in."""
    by = defaultdict(list)
    for n in prompts:
        by[min(b for b in buckets if b >= n)].append(n)
    return list(by.values())


def read(run):
    tr = run.trace
    if tr is None or run.win.get("traced_from") is None:
        return None
    dm = run.result["dims"]
    buckets = run.cfg["engine"]["prefill_buckets"]
    spans = sorted(tr.spans_named("bench.step_once"), key=lambda s: s.start)
    ticks = run.win["ticks"][run.win["traced_from"]:]
    least = device = 0.0
    for s, t in zip(spans, ticks):
        if not t["prefill"]:
            continue
        ops = tr.ops_within(s.start, s.end, KERNEL)
        if not ops:
            continue
        device += tr.device_s(ops)
        for g in groups(t["prompts"], buckets):
            f, b = counts.flash_attn_least(dm, g)
            sec, _ = counts.roofline_time(f, b, run.peaks["bf16_flops_per_s"],
                                          run.peaks["hbm_bytes_per_s"])
            least += sec * dm.layers
    return 100 * least / device if device else None
