"""Elastic fleet controller on the deterministic fleet sim — the PR 7
closed loop, end to end:

1. a seeded flash-crowd trace (Poisson arrivals, 6x rate surge for a
   window, then a long trough) is offered to TWO fleets at identical
   load: a fixed 4-replica fleet and an elastic fleet that starts at 2,
2. the elastic fleet's ``FleetController`` watches queue depth, shed
   rate, and SLA-miss fraction each control tick and scales up through
   the engine factory / down through ``drain_replica`` — the SAME path
   a card fault takes, so departures are always zero-loss,
3. mid-crowd one replica freezes (stops serving AND heartbeating); the
   ``HeartbeatMonitor`` edge signal fires exactly once and the
   controller drains the dead card's backlog onto the survivors,
4. the trough then shrinks the fleet back down (EWMA-smoothed
   sustained-underload hysteresis, so Poisson blips don't flap it).

The punchline, held as a bound by
``tests/test_controller.py::test_fleet_sim_scenario_within_bounds``:
at equal offered load the elastic fleet sheds LESS at the
peak than the fixed fleet AND burns fewer replica-seconds across the
trough — and nothing is ever lost across any scale or fault event.

Run: PYTHONPATH=src python examples/serve_elastic.py
"""
from repro.serving.fleet_sim import elastic_vs_fixed

r = elastic_vs_fixed(kill_at_frac=0.33)
ctl = r["controller"]
n = len(r["arrivals"])

print(f"offered: {n} requests, flash crowd 6x between 25% and 40% of "
      f"the trace, one replica frozen mid-crowd\n")

# -- the controller's decision log: every scale event, why, and when -------
print("controller timeline (scale + fault events):")
for d in ctl.decisions:
    if d.action == "hold":
        continue
    print(f"  t={d.now:7.3f}s  {d.action:12s} replica={d.replica} "
          f"live={d.live}  [{d.reason}]")

# -- the comparison the elastic scenario bounds ---------------------------
fx, el = r["fixed"], r["elastic"]
print(f"\n{'':14s}{'fixed(4)':>10s}{'elastic(2..8)':>14s}")
print(f"{'shed':14s}{fx['shed']:>10d}{el['shed']:>14d}")
print(f"{'completed':14s}{fx['completed']:>10d}{el['completed']:>14d}")
print(f"{'replica-sec':14s}{r['replica_seconds_fixed']:>10.1f}"
      f"{r['replica_seconds_elastic']:>14.1f}")
print(f"{'lost':14s}{fx['lost']:>10d}{el['lost']:>14d}")

print(f"\nscale-ups={ctl.scale_ups} scale-downs={ctl.scale_downs} "
      f"faults drained={ctl.faults_drained} "
      f"peak live={r['elastic']['peak_live']} "
      f"trough mean live={r['trough_live_mean']:.2f}")

assert r["shed_improved"], "elastic must shed less at the peak"
assert r["capacity_improved"], "elastic must burn fewer replica-seconds"
assert r["zero_lost"], "no ticket may be lost across scale/fault events"
assert ctl.faults_drained == 1
print("\nOK: sheds less at peak, cheaper through the trough, zero lost.")
