"""Fleet-simulation property harness (PR 4 work stealing).

The simulator core lives in ``repro.serving.fleet_sim`` (it is runtime
infrastructure: the elastic smoke and example run it too); this
module is the test-facing surface — re-exports plus the seeded
random-schedule driver the property suite uses to push the fleet through
thousands of submit / steal / fail / complete interleavings with zero
wall-clock flakiness. Everything is keyed off one ``numpy`` Generator, so
a fixed seed reproduces the exact schedule, completion order, and steal
attribution.
"""
import numpy as np

from repro.serving.fleet_sim import FleetSim, SimReplica  # noqa: F401


def random_schedule(sim: FleetSim, n_ops: int, *, p_submit: float = 0.55,
                    skew: float = 0.0, hot: int = 0,
                    fail_at: int = -1, slo_ms=None,
                    max_priority: int = 0, p_page: float = 0.0,
                    p_migrate: float = 0.0) -> int:
    """Drive ``sim`` through ``n_ops`` seeded events: each op is a submit
    (probability ``p_submit``; pinned to replica ``hot`` with probability
    ``skew`` — the hot-keyed stream) or a tick; op ``fail_at`` (if in
    range and a live sibling remains) kills the currently most-loaded
    live replica mid-run. With probability ``p_page`` / ``p_migrate``
    each op ALSO fires a movable-state event (PR 8) before the
    submit-or-tick: a page_out or page_in on a random replica, or a
    migrate between a random (src, dst) pair — conservation and ticket
    identity must survive any interleaving of these with steals, fails,
    and drains. Returns the index of the failed replica (-1 if none).
    The caller drains and asserts afterwards."""
    failed = -1
    n = len(sim.replicas)
    for op in range(n_ops):
        if op == fail_at and len(sim.router.alive) > 1:
            alive = sim.router.alive
            failed = max(alive, key=lambda i: (sim.router.load(i), i))
            sim.fail(failed)
        if p_page > 0 and sim.rng.random() < p_page:
            idx = int(sim.rng.integers(0, n))
            if sim.rng.random() < 0.5:
                sim.page_out(idx)
            else:
                sim.page_in(idx)
        if p_migrate > 0 and sim.rng.random() < p_migrate:
            sim.migrate(int(sim.rng.integers(0, n)),
                        int(sim.rng.integers(0, n)))
        if sim.rng.random() < p_submit:
            pin = None
            if skew > 0 and sim.rng.random() < skew \
                    and not sim.router.dead[hot]:
                pin = hot
            sim.submit(size=int(sim.rng.integers(1, 8)),
                       priority=int(sim.rng.integers(0, max_priority + 1)),
                       slo_ms=slo_ms, pin=pin)
        else:
            sim.tick()
    return failed


def run_to_completion(sim: FleetSim) -> list:
    """Drain the fleet and return the completion order as payload ids
    (the determinism fingerprint, together with steal attribution)."""
    sim.drain()
    return [t.payload for t in sim.completed]


def make_controller(sim: FleetSim, *, min_replicas: int = 1,
                    max_replicas: int = 8, cooldown_s: float = 0.2,
                    down_hold_s: float = 0.5, timeout_s: float = 0.05,
                    service_s: float = 0.01, **cfg_kw):
    """Wire a FleetController to ``sim`` (PR 7 elastic tests): heartbeat
    monitor on the sim's virtual clock, scale-up factory building
    replicas that join the sim's conservation tracking."""
    from repro.runtime.fault_tolerance import HeartbeatMonitor
    from repro.serving.controller import ControllerConfig, FleetController
    mon = HeartbeatMonitor(num_hosts=len(sim.replicas),
                           timeout_s=timeout_s, clock=lambda: sim.now)
    return FleetController(
        sim.router, sim.replica_factory(service_s=service_s), mon,
        ControllerConfig(min_replicas=min_replicas,
                         max_replicas=max_replicas, cooldown_s=cooldown_s,
                         down_hold_s=down_hold_s, **cfg_kw))
