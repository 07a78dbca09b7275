"""Model step, decode (the ``jit_decode_step`` program): over the traced
ticks that ran only a decode step, the least bytes such a step must read
(every layer's weights and the head once per step, plus the live K/V of
each decoding sequence, counted as ``lm.decode_hbm_roofline`` counts them)
over the device time of ``jit_decode_step`` in those ticks x HBM bytes/s.
Base: the decode program's own device time, without the host round trip
between steps. Moves ``itl_p95_ms``."""
from bench import counts
from bench.program_spans import (DECODE, decode_only, device_s,
                                 program_ops, traced_ticks)


def read(run):
    dm = run.result["dims"]
    weights = counts.decode_step_bytes(dm, [])
    nbytes = device = 0.0
    for span, t in traced_ticks(run):
        if not decode_only(t):
            continue
        ops = program_ops(run.trace, span, DECODE)
        if not ops:
            continue
        device += device_s(run.trace, ops)
        nbytes += weights * t["decode_steps"] \
            + sum(t["contexts"]) * counts.kv_bytes_per_position(dm)
    if not device:
        return None
    return 100 * nbytes / (device * run.peaks["hbm_bytes_per_s"])
