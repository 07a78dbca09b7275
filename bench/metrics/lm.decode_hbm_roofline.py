"""Model step, decode (models/attention.py, models/model.py): over ticks
that ran only a decode step, the least bytes such a step must read (every
layer's weights and the head once per replica that stepped, plus the live
K/V of each decoding sequence) over the ticks' host wall x HBM bytes/s x
chips. Ticks before the traced stretch only. Moves ``itl_p95_ms``."""
from bench import counts


def read(run):
    dm = run.result["dims"]
    weights = counts.decode_step_bytes(dm, [])
    nbytes = wall = 0.0
    for t in run.win["ticks"]:
        if t["prefill"] or not t["decode_steps"] \
                or t["t0"] >= run.win["untraced_until"]:
            continue
        nbytes += weights * t["decode_steps"] \
            + sum(t["contexts"]) * counts.kv_bytes_per_position(dm)
        wall += t["t1"] - t["t0"]
    if not wall:
        return None
    return 100 * nbytes / (wall * run.peaks["hbm_bytes_per_s"]
                           * run.cell["chips"])
