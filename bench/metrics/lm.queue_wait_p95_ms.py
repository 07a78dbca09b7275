"""Scheduler (serving/scheduler.py): p95 of the wait from a request's due
time to its first admission, ``Ticket.admit_t``; a request not admitted by
the close enters at its age then. Moves ``ttft_p95_ms``."""
from bench.drivers.common import percentile


def read(run):
    close = run.win["close"]
    waits = []
    for r in run.win["recs"]:
        if r.due >= close:
            continue
        a = getattr(r.ticket, "admit_t", None) if r.ticket else None
        waits.append(((a if a is not None and a <= close else close)
                      - r.due) * 1e3)
    return percentile(waits, 0.95) if waits else None
