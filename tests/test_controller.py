"""Elastic fleet controller (PR 7): scenario tests on the deterministic
fleet sim — production-shaped traces through the closed control loop
(heartbeat detector -> controller -> one drain path), plus the fleet-sim
scenarios whose tail, spread and capacity bounds hold the fleet's wins.

The property suite (test_scheduler_properties.py) holds the invariants
under random interleavings; this file pins the named scenarios the fleet
claims: the 10^5-request flash crowd where autoscaling sheds strictly
less than a fixed fleet at equal offered load, missed-heartbeat and
deliberate scale-down both draining with zero loss, replace-then-drain
on the last live replica, no flapping under steady load, and the steal,
router and elastic scenarios held within their bounds.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from fleet_sim import FleetSim, make_controller  # noqa: E402

from repro.serving.fleet_sim import (diurnal_trace,  # noqa: E402
                                     elastic_vs_fixed, flash_crowd_trace,
                                     hot_burst_trace, multi_tenant_trace,
                                     run_elastic, run_fixed)


# ---- the headline: 10^5-request flash crowd -------------------------------

def test_flash_crowd_100k_autoscale_beats_fixed_fleet():
    """>= 10^5 simulated requests through the closed loop: at equal
    offered load the autoscaled fleet must shed STRICTLY less at the
    flash-crowd peak than the fixed fleet, burn fewer replica-seconds
    across the diurnal trough, and lose nothing across every scale
    event."""
    r = elastic_vs_fixed(n=100_000)
    assert len(r["arrivals"]) >= 100_000
    assert r["elastic"]["shed"] < r["fixed"]["shed"]
    assert r["replica_seconds_elastic"] < r["replica_seconds_fixed"]
    assert r["zero_lost"]
    ctl = r["controller"]
    assert ctl.scale_ups >= 1 and ctl.scale_downs >= 1
    # conservation was asserted inside both arms (sim.assert_conserved);
    # re-state the fleet-level identity on the returned counts
    for arm in (r["elastic"], r["fixed"]):
        assert arm["accepted"] == arm["completed"]


# ---- fault path: missed heartbeat -> the one drain path -------------------

def _crowd(n=2_000, seed=2, **kw):
    return flash_crowd_trace(n, base_gap_s=0.006, crowd_x=6.0, seed=seed,
                             slo_ms=500.0, **kw)


def test_missed_heartbeat_drains_exactly_once_with_zero_loss():
    """A frozen card stops serving AND heartbeating; the detector's edge
    signal fires once, the controller drains through router.drain_replica
    (same path as deliberate scale-down), and every ticket the dead card
    held is re-homed and completed."""
    sim = FleetSim(replicas=2, service_s=0.01, slots=1, dt=0.005, seed=1,
                   max_queue=64)
    ctl = make_controller(sim, min_replicas=2, max_replicas=6)
    arr = _crowd()
    kill_t = arr[len(arr) // 2].t       # mid-crowd: min_replicas=2 pins
    m = run_elastic(sim, ctl, arr, kills=[(kill_t, 0)])
    assert ctl.faults_drained == 1
    assert sim.router.dead[0]
    assert 0 not in ctl.monitor.hosts   # deregistered after the drain
    drains = [d for d in ctl.decisions if d.action == "drain_failed"]
    assert len(drains) == 1 and drains[0].replica == 0
    assert m["lost"] == 0 and m["accepted"] == m["completed"]
    # the dead card's queue went somewhere: the fleet counted a drain
    assert sim.router.fleet_telemetry().drained > 0


def test_replace_then_drain_when_fault_hits_last_live_replica():
    """A fault on the ONLY live replica must not leave the drain without
    a destination: the controller registers a factory replacement first
    (decision 'replace'), then drains — zero loss, fleet still serving."""
    sim = FleetSim(replicas=1, service_s=0.01, slots=1, dt=0.005, seed=3,
                   max_queue=64)
    # up-trigger disabled: the fleet must still be the single replica
    # when the fault lands, so the fault IS the last-live case
    ctl = make_controller(sim, min_replicas=1, max_replicas=2,
                          up_queue_per_replica=1e9)
    arr = diurnal_trace(400, base_gap_s=0.02, amp=0.0, seed=3)
    m = run_elastic(sim, ctl, arr, kills=[(arr[200].t, 0)])
    acts = [d.action for d in ctl.decisions if d.action != "hold"]
    i_rep, i_drain = acts.index("replace"), acts.index("drain_failed")
    assert i_rep < i_drain              # replacement registered BEFORE
    assert sim.router.dead[0] and len(sim.router.alive) >= 1
    assert m["lost"] == 0 and m["accepted"] == m["completed"]


# ---- deliberate scale-down: same drain path -------------------------------

def test_scale_down_goes_through_drain_path_and_deregisters():
    """Scale-down victims are drained via router.drain_replica (dead,
    re-homed, zero loss) and leave the heartbeat monitor, so a parked
    card is never later mistaken for a death."""
    sim = FleetSim(replicas=4, service_s=0.01, slots=1, dt=0.005, seed=5,
                   max_queue=64)
    ctl = make_controller(sim, min_replicas=1, max_replicas=4)
    arr = diurnal_trace(600, base_gap_s=0.03, amp=0.0, seed=5)  # light
    m = run_elastic(sim, ctl, arr)
    downs = [d for d in ctl.decisions if d.action == "down"]
    assert downs, "light load on 4 replicas must scale down"
    for d in downs:
        assert sim.router.dead[d.replica]
        assert d.replica not in ctl.monitor.hosts
        assert d.live >= 1
    assert ctl.faults_drained == 0      # departures are not deaths
    assert m["lost"] == 0 and m["accepted"] == m["completed"]


def test_scale_up_joins_router_and_stealing_rebalances():
    """Scale-up registers a fresh replica (telemetry counts scaled_in);
    work stealing then pulls the existing backlog onto it — the new
    card must end up having served real work, with no dedicated
    migration machinery."""
    sim = FleetSim(replicas=2, service_s=0.01, slots=1, dt=0.005, seed=1,
                   max_queue=64)
    ctl = make_controller(sim, min_replicas=2, max_replicas=6)
    m = run_elastic(sim, ctl, _crowd())
    assert ctl.scale_ups >= 1
    joined = list(range(2, 2 + ctl.scale_ups))
    assert [sim.replicas[j].telemetry.scaled_in for j in joined] \
        == [1] * len(joined)
    assert m["fleet"]["scaled_in"] == ctl.scale_ups
    assert sum(sim.replicas[j].telemetry.served for j in joined) > 0
    assert sim.router.fleet_telemetry().steals > 0
    assert m["lost"] == 0


# ---- hysteresis: steady load must not flap --------------------------------

def test_steady_load_does_not_flap():
    """Steady moderate load (no crowd, no trough) for a long window:
    the cooldown + sustained-underload hysteresis must hold the fleet
    essentially still — a handful of scale events at most, not the
    up/down oscillation a single-sample threshold produces."""
    sim = FleetSim(replicas=2, service_s=0.01, slots=1, dt=0.005, seed=7,
                   max_queue=64)
    ctl = make_controller(sim, min_replicas=1, max_replicas=6)
    arr = diurnal_trace(5_000, base_gap_s=0.009, amp=0.0, seed=7,
                        slo_ms=500.0)      # rho ~ 0.75 on 2 replicas...
    m = run_elastic(sim, ctl, arr)
    assert m["lost"] == 0
    assert ctl.scale_ups + ctl.scale_downs <= 4, (
        f"flapping: +{ctl.scale_ups}/-{ctl.scale_downs} under steady load")


# ---- PR 10 signals: cache pressure and the predictive wait forecast -------

def test_cache_pressure_gates_scale_up():
    """Host-RAM paging pressure is an up signal when (and only when) the
    ``up_cache_pressure`` gate is configured: cards spilling KV state to
    host RAM mean the fleet is short on resident slots even with a calm
    queue."""
    sim = FleetSim(replicas=2, service_s=0.01, slots=2, dt=0.005, seed=9,
                   max_queue=64)
    for _ in range(4):
        sim.submit(size=2)
    sim.tick()                          # admit into the slots
    for i in range(2):
        sim.page_out(i)                 # 1 of 2 slots paged on each card
    # gate unset (default): pressure is visible but never an up reason
    off = make_controller(sim, min_replicas=2, max_replicas=4,
                          up_queue_per_replica=1e9)
    sig = off.signals(sim.now)
    assert sig["cache_pressure"] == pytest.approx(0.5)
    off.step(sim.now)
    assert off.scale_ups == 0
    # gate set below the observed pressure: scale-up, with the reason
    on = make_controller(sim, min_replicas=2, max_replicas=4,
                         up_queue_per_replica=1e9, up_cache_pressure=0.4)
    made = on.step(sim.now)
    ups = [d for d in made if d.action == "up"]
    assert len(ups) == 1 and "cache pressure" in ups[0].reason
    assert on.scale_ups == 1 and len(sim.router.alive) == 3
    sim.drain()
    sim.assert_conserved()


def test_wait_forecast_fires_before_any_ewma_is_measured():
    """With a PerfModel attached the scale-up wait gate switches from
    the reactive EWMA estimate (silent until completions land) to the
    predictive forecast — model-predicted decode step x queue depth —
    so a cold fleet staring at a backlog scales up on the FIRST control
    step, before serving a single request."""
    from repro.runtime.fault_tolerance import HeartbeatMonitor
    from repro.serving.controller import ControllerConfig, FleetController
    from repro.serving.perf_model import PerfModel

    sim = FleetSim(replicas=2, service_s=0.01, slots=1, dt=0.005, seed=13,
                   max_queue=64)
    for _ in range(24):
        sim.submit(size=1)              # backlog, nothing served yet
    pm = PerfModel(1e9)
    pm.set_dispatch_cost("decode", 50e-3, 0.0)   # 50 ms predicted step
    cfg = ControllerConfig(min_replicas=2, max_replicas=4,
                           up_queue_per_replica=1e9, slo_ms=100.0,
                           up_wait_ratio=1.0)

    def mk(perf_model):
        mon = HeartbeatMonitor(num_hosts=len(sim.replicas), timeout_s=10.0,
                               clock=lambda: sim.now)
        return FleetController(sim.router,
                               sim.replica_factory(service_s=0.01), mon,
                               cfg, perf_model=perf_model)

    reactive = mk(None)
    sig = reactive.signals(sim.now)
    assert sig["est_wait_ms"] == 0.0    # no completions -> no EWMAs
    assert sig["wait_forecast_ms"] == 0.0
    reactive.step(sim.now)
    assert reactive.scale_ups == 0      # reactive gate is blind here

    predictive = mk(pm)
    sig = predictive.signals(sim.now)
    # 24 queued / 2 live x 50 ms predicted step = 600 ms forecast
    assert sig["wait_forecast_ms"] == pytest.approx(600.0)
    made = predictive.step(sim.now)
    ups = [d for d in made if d.action == "up"]
    assert len(ups) == 1 and "forecast wait" in ups[0].reason
    assert predictive.scale_ups == 1
    sim.drain()
    sim.assert_conserved()


# ---- production-shaped traces: the whole mix ------------------------------

def test_hot_burst_and_multi_tenant_traces_conserve():
    """Hot-keyed burst (session-affinity pins survive replica death via
    re-route) and multi-tenant priority mix both run the closed loop to
    empty with zero loss."""
    sim = FleetSim(replicas=3, service_s=0.01, slots=1, dt=0.005, seed=11,
                   max_queue=64)
    ctl = make_controller(sim, min_replicas=2, max_replicas=6)
    arr = hot_burst_trace(2_000, base_gap_s=0.005, hot=0, seed=11,
                          slo_ms=500.0)
    m = run_elastic(sim, ctl, arr, kills=[(arr[len(arr) // 2].t, 0)])
    assert m["lost"] == 0 and ctl.faults_drained == 1

    sim2 = FleetSim(replicas=2, service_s=0.01, slots=1, dt=0.005,
                    seed=13, max_queue=64)
    ctl2 = make_controller(sim2, min_replicas=1, max_replicas=6)
    m2 = run_elastic(sim2, ctl2, multi_tenant_trace(2_000,
                                                    base_gap_s=0.007,
                                                    seed=13))
    assert m2["lost"] == 0


# ---- fleet-sim scenario bounds --------------------------------------------
#
# Named scenarios on the bit-deterministic virtual-clock fleet sim: a metric
# that moves moved because the code changed. Each bound is (metric, "max" or
# "min", limit); a boolean claim is held as min 1.

def _drive(sim, arrivals, rng, pin_hot=None):
    i = 0
    while i < len(arrivals) or sim.router.has_work:
        while i < len(arrivals) and arrivals[i] <= sim.now:
            hot = pin_hot is not None and rng.random() < pin_hot
            sim.submit(pin=0 if hot else None)
            i += 1
        sim.tick()
    sim.assert_conserved()


def _scenario_steal():
    """Hot-keyed stream (80% pinned to replica 0) on a 3-replica fleet:
    tail latency and completed-work spread with stealing on, and both
    improved against the same stream with stealing off."""
    def one(steal):
        sim = FleetSim(replicas=3, service_s=0.01, slots=1, steal=steal,
                       dt=0.0025, seed=0)
        rng = np.random.default_rng(1)
        _drive(sim, np.cumsum(rng.exponential(0.004, 120)), rng,
               pin_hot=0.8)
        served = sim.served_per_replica()
        return sim.fleet_summary(), max(served) - min(served)

    no_steal, spread_ns = one(False)
    steal, spread_s = one(True)
    return {"p99_ms": steal["latency_ms_p99"],
            "spread": spread_s,
            "p99_improved": steal["latency_ms_p99"]
            < no_steal["latency_ms_p99"],
            "spread_improved": spread_s < spread_ns}


def _scenario_router():
    """Queueing relief from fleet width: 1 vs 2 replicas at the same
    offered load, just past one replica's capacity."""
    def one(replicas):
        sim = FleetSim(replicas=replicas, service_s=0.01, slots=1,
                       dt=0.0025, seed=0)
        rng = np.random.default_rng(5)
        _drive(sim, np.cumsum(rng.exponential(0.008, 200)), rng)
        return sim.fleet_summary()

    single, dual = one(1), one(2)
    return {"dual_p99_ms": dual["latency_ms_p99"],
            "p99_ratio": dual["latency_ms_p99"]
            / max(single["latency_ms_p99"], 1e-9),
            "p99_improved": dual["latency_ms_p99"]
            < single["latency_ms_p99"]}


def _scenario_elastic():
    """Autoscaled vs fixed fleet on the same flash-crowd trace: less
    shedding at the peak, fewer replica-seconds, nothing lost."""
    r = elastic_vs_fixed()
    slo_ms = 500.0
    return {"p99_ms": r["elastic"]["fleet"]["latency_ms_p99"],
            "p99_vs_slo": r["elastic"]["fleet"]["latency_ms_p99"] / slo_ms,
            "shed_elastic": r["elastic"]["shed"],
            "shed_ratio": r["elastic"]["shed"]
            / max(r["fixed"]["shed"], 1),
            "replica_seconds": r["replica_seconds_elastic"],
            "replica_seconds_ratio": r["replica_seconds_elastic"]
            / max(r["replica_seconds_fixed"], 1e-9),
            "lost": r["elastic"]["lost"] + r["fixed"]["lost"],
            "shed_improved": r["shed_improved"],
            "capacity_improved": r["capacity_improved"]}


FLEET_SIM_BOUNDS = {
    "steal": (_scenario_steal, [
        ("p99_ms", "max", 70.6875), ("spread", "max", 10.0),
        ("p99_improved", "min", 1), ("spread_improved", "min", 1)]),
    "router": (_scenario_router, [
        ("dual_p99_ms", "max", 59.375), ("p99_ratio", "max", 0.0781),
        ("p99_improved", "min", 1)]),
    "elastic": (_scenario_elastic, [
        ("p99_ms", "max", 412.5), ("p99_vs_slo", "max", 0.825),
        ("shed_elastic", "max", 135.0), ("shed_ratio", "max", 0.5895),
        ("replica_seconds", "max", 97.5187),
        ("replica_seconds_ratio", "max", 1.0), ("lost", "max", 0.0),
        ("shed_improved", "min", 1), ("capacity_improved", "min", 1)]),
}


@pytest.mark.parametrize("name", sorted(FLEET_SIM_BOUNDS))
def test_fleet_sim_scenario_within_bounds(name):
    scenario, bounds = FLEET_SIM_BOUNDS[name]
    measured = scenario()
    bad = []
    for metric, kind, limit in bounds:
        v = float(measured[metric])
        if (v > limit) if kind == "max" else (v < limit):
            bad.append(f"{metric}={v:.6g} ({kind} {limit})")
    assert not bad, f"{name}: {bad}; measured {measured}"
