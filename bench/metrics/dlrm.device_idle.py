"""Device: the share of the traced stretch in which no operation ran on
the device, 100 x (1 - union of op intervals / stretch). Moves
``rank_p95_ms``."""


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    return 100 * (1 - tr.busy_s / tr.window_s)
