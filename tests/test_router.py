"""ReplicaRouter: routing rule, fleet telemetry aggregation, concurrent-
drain semantics, and priority/shedding through real engines."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models import model as M
from repro.serving.engine import InferenceEngine, Request, make_replicas
from repro.serving.router import ReplicaRouter, spread
from repro.serving.telemetry import Telemetry, percentile


from conftest import StubReplica as _Stub  # noqa: E402


# ---- routing rule ---------------------------------------------------------

def test_routes_to_least_loaded():
    router = ReplicaRouter([_Stub(), _Stub(), _Stub()])
    # preload replica 0 with 2 tickets, replica 1 with 1, out of band
    router.replicas[0].submit("x"); router.replicas[0].submit("y")
    router.replicas[1].submit("z")
    t = router.submit("new")
    assert t.tid == 0                       # replica 2's first ticket
    assert router.replicas[2].scheduler.depth == 1


def test_deadline_tiebreak_spreads_urgent_traffic():
    router = ReplicaRouter([_Stub(), _Stub()])
    # equal loads (1 each) but replica 0 holds the deadline ticket
    router.replicas[0].submit("d", slo_ms=50.0)
    router.replicas[1].submit("b")
    router.submit("urgent", slo_ms=10.0)
    assert router.replicas[1].scheduler.depth == 2   # spread, not piled


def test_round_robin_on_full_ties():
    router = ReplicaRouter([_Stub(), _Stub(), _Stub()])
    for i in range(6):
        router.submit(i)
    assert router.routed == [2, 2, 2]
    assert spread(router) == 0


def test_router_requires_replicas():
    with pytest.raises(ValueError):
        ReplicaRouter([])


# ---- EWMA feedback routing (PR 3 satellite) -------------------------------

def test_feedback_routing_starves_slow_replica_proportionally():
    """route="feedback": with measured per-replica step times folded into
    the EWMA, a replica 3x slower than its sibling settles at roughly
    1/3 of the traffic under a pure submit sequence (cost = (load+1) x
    EWMA), instead of the half that count-based routing would give."""
    router = ReplicaRouter([_Stub(), _Stub()], route="feedback")
    router.record_dispatch(0, 0.010)            # fast card: 10 ms steps
    router.record_dispatch(1, 0.030)            # slow card: 30 ms steps
    n = 60
    for i in range(n):
        router.submit(i)
    fast, slow = router.routed
    assert fast + slow == n
    assert slow < fast                           # less traffic, full stop
    # proportionality: cost balance implies fast/slow ~ 3; allow slack
    # for the integer lattice but rule out count-balance (30/30)
    assert slow <= fast / 2
    assert abs(fast - 3 * slow) <= 4


def test_feedback_routing_without_measurements_degrades_to_count():
    router = ReplicaRouter([_Stub(), _Stub(), _Stub()], route="feedback")
    for i in range(9):
        router.submit(i)
    assert router.routed == [3, 3, 3]
    assert spread(router) == 0


def test_feedback_unmeasured_replica_charged_fleet_mean():
    """A replica with no EWMA sample yet neither hoards traffic (cost 0)
    nor starves: it is charged the fleet-mean step time."""
    router = ReplicaRouter([_Stub(), _Stub()], route="feedback")
    router.record_dispatch(0, 0.020)            # only replica 0 measured
    for i in range(20):
        router.submit(i)
    assert min(router.routed) >= 8               # near-even split


def test_feedback_ewma_folds_measurements():
    router = ReplicaRouter([_Stub()], route="feedback", ewma_alpha=0.5)
    router.record_dispatch(0, 0.010)
    assert router.ewma_s[0] == pytest.approx(0.010)
    router.record_dispatch(0, 0.030)
    assert router.ewma_s[0] == pytest.approx(0.020)


def test_drive_loops_feed_the_ewma(lm_setup):
    cfg, params = lm_setup
    reps = make_replicas(cfg, params, 2, batch_slots=2, max_len=32,
                         prefill_buckets=(8, 16))
    router = ReplicaRouter(reps, route="feedback")
    for r in _trace(cfg):
        router.submit(r)
    router.run_until_drained()
    assert all(e > 0 for e in router.ewma_s)
    assert router.summary()["route"] == "feedback"


def test_router_rejects_unknown_route():
    with pytest.raises(ValueError):
        ReplicaRouter([_Stub()], route="fastest")


# ---- work stealing + fault drain (PR 4) -----------------------------------

def test_steal_moves_backlog_to_idle_replica_no_double_count():
    """A steal that lands a ticket on an idle replica must move it, not
    copy it: fleet-wide outstanding load (the PR 3 fresh_depth
    accounting) is unchanged, the victim stops counting the ticket, and
    the steal is attributed to the thief."""
    router = ReplicaRouter([_Stub(), _Stub()], steal=True)
    for i in range(6):
        router.replicas[0].submit(i)            # hot-keyed stream
    before = sum(router.load(i) for i in range(2))
    moved = router.maybe_steal()
    assert moved >= 1
    assert router.replicas[1].scheduler.depth == moved
    assert router.replicas[0].scheduler.depth == 6 - moved
    assert sum(router.load(i) for i in range(2)) == before  # no double count
    assert router.replicas[1].telemetry.steals == moved     # thief's counter
    assert router.replicas[0].telemetry.steals == 0
    assert router.steals_per_replica == [0, moved]
    assert router.fleet_telemetry().steals == moved
    assert "steals" in router.summary() and \
        router.summary()["steals_per_replica"] == [0, moved]


def test_steal_disabled_by_default_and_busy_thief_never_steals():
    router = ReplicaRouter([_Stub(), _Stub()])
    router.replicas[0].submit("x")
    router.replicas[0].submit("y")
    assert router.maybe_steal() == 0            # steal=False: no-op
    stealing = ReplicaRouter([_Stub(), _Stub()], steal=True)
    stealing.replicas[0].submit("x")
    stealing.replicas[1].submit("y")            # thief has its own queue
    assert stealing.maybe_steal() == 0


def test_stolen_ticket_latency_measured_from_original_submit():
    """TTFT / latency boundary: the stolen ticket keeps its original
    enqueue stamp on a shared clock, so time-to-first-token and latency
    are measured from the ORIGINAL submit, not from steal time."""
    from repro.serving.scheduler import Scheduler
    victim, thief = Scheduler("fifo"), Scheduler("fifo")
    t = victim.submit("r", now=0.0)
    stolen = victim.steal_pending(1, now=5.0)
    thief.absorb(stolen, now=5.0)
    assert t.enqueue_t == 0.0                   # steal did not re-base
    got = thief.admit(1, now=5.0)
    thief.complete(got[0], now=6.0)
    assert got[0].latency_ms == pytest.approx(6000.0)   # not 1000


def test_drain_replica_rehomes_pending_and_marks_dead():
    router = ReplicaRouter([_Stub(), _Stub()])
    for i in range(5):
        router.replicas[0].submit(i)
    router.replicas[1].submit("own")
    moved = router.drain_replica(0)
    assert moved == 5
    assert router.dead == [True, False]
    assert router.replicas[0].scheduler.depth == 0
    assert router.replicas[1].scheduler.depth == 6
    assert router.replicas[0].telemetry.drained == 5    # victim's counter
    assert router.fleet_telemetry().drained == 5
    assert router.rehomed == [0, 5]
    assert router.drain_replica(0) == 0                 # idempotent
    router.submit("new")                                # routes around dead
    assert router.replicas[1].scheduler.depth == 7
    with pytest.raises(RuntimeError):
        router.drain_replica(1)         # nowhere left to re-home 7 tickets


def test_add_replica_joins_live_routing_and_counts_scaled_in():
    """Elastic scale-up (PR 7): add_replica appends every per-replica
    array in lockstep, the join shows up in telemetry as one scaled_in,
    and the fresh replica takes traffic immediately — no dedicated
    warm-up or migration path."""
    router = ReplicaRouter([_Stub(), _Stub()])
    fresh = _Stub(precision="w8a8")
    idx = router.add_replica(fresh)
    assert idx == 2 and idx in router.alive and not router.dead[idx]
    assert (len(router.ewma_s) == len(router.routed) == len(router.dead)
            == len(router.steals_per_replica) == len(router.rehomed)
            == len(router.clock_offset) == len(router.precisions) == 3)
    assert router.precisions[idx] == "w8a8"
    assert fresh.telemetry.scaled_in == 1
    assert router.fleet_telemetry().scaled_in == 1
    for _ in range(3):
        router.submit("p", priority=1)  # class 1: no fp32 precision pin
    assert router.routed == [1, 1, 1]   # least-loaded: joiner pulls weight


def test_add_replica_late_joiner_rebases_rehomed_ticket_stamps():
    """A late joiner on its own timeline declares clock_offset; tickets
    re-homed onto it shift enqueue/deadline stamps by exactly that
    offset (Scheduler.absorb from_now contract), so age and deadline
    slack survive the timeline change. A shared-clock joiner's stamps
    move untouched."""
    router = ReplicaRouter([_Stub()])
    t = router.submit("x", slo_ms=1000.0)
    j = router.add_replica(_Stub(), clock_offset=50.0)
    enq, dl = t.enqueue_t, t.deadline_t
    assert router.drain_replica(0) == 1
    assert router.rehomed[j] == 1
    assert t.enqueue_t == pytest.approx(enq + 50.0)
    assert t.deadline_t == pytest.approx(dl + 50.0)

    same = ReplicaRouter([_Stub()])
    t2 = same.submit("y", slo_ms=1000.0)
    same.add_replica(_Stub())           # clock_offset defaults to 0
    enq2, dl2 = t2.enqueue_t, t2.deadline_t
    assert same.drain_replica(0) == 1
    assert t2.enqueue_t == enq2 and t2.deadline_t == dl2


def test_lm_fleet_steals_under_hot_spot_and_survives_mid_run_kill(lm_setup):
    """End-to-end through real LM engines: a hot-spot stream on replica 0
    gets stolen by idle replica 1; killing replica 0 mid-run re-homes
    its outstanding work and every request still finishes (zero lost
    tickets through the fault — conservation holds)."""
    cfg, params = lm_setup
    reps = make_replicas(cfg, params, 2, batch_slots=2, max_len=32,
                         prefill_buckets=(8, 16))
    router = ReplicaRouter(reps, steal=True)
    reqs = _trace(cfg)
    for r in reqs:
        reps[0].submit(r)                       # all pinned to one card
    rounds = 0
    while router.has_work:
        router.maybe_steal()
        for i, rep in enumerate(router.replicas):
            if not router.dead[i] and rep.has_work:
                rep.step_once()
        rounds += 1
        if rounds == 2:
            router.drain_replica(0)
    fleet = router.fleet_telemetry()
    assert all(r.done for r in reqs)            # zero lost through the kill
    assert fleet.served == len(reqs)
    assert fleet.steals > 0
    assert fleet.drained > 0
    assert router.dead == [True, False]
    assert not reps[0].has_work and reps[0].free_slots == 2


def test_lm_engine_steal_eligibility_vetoes_mid_prefill():
    """The engine hook: fresh tickets are stealable, continuations and
    mid-prefill tickets (KV slot holders) are not."""
    from repro.serving.scheduler import Ticket
    cfg = reduce_for_smoke(get_config("deepseek-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=32,
                          prefill_buckets=(8, 16))
    fresh = Ticket(0, None)
    cont = Ticket(1, None, continuation=True)
    midprefill = Ticket(2, None)
    eng.prefilling[id(midprefill)] = 0      # keyed by object, not tid:
    collider = Ticket(2, None)              # a stolen ticket may reuse a
    assert eng.steal_eligible(fresh)        # sibling scheduler's tid
    assert not eng.steal_eligible(cont)
    assert not eng.steal_eligible(midprefill)
    assert eng.steal_eligible(collider)


def test_steal_with_chunked_prefill_tid_collision_is_safe(lm_setup):
    """Regression: tids are per-scheduler counters, so a stolen fresh
    ticket can carry the SAME tid as a ticket mid-prefill on the thief.
    KV-slot ownership is keyed by ticket identity, not tid — with a
    tid-keyed map the stolen prompt, admitted in its own chunk group
    (different bucket) while the long prompt was still mid-prefill,
    inherited the mid-prefill ticket's KV slot and the long prompt
    silently decoded garbage."""
    cfg, params = lm_setup
    kw = dict(batch_slots=2, max_len=32, prefill_buckets=(2, 4, 16))
    reps = make_replicas(cfg, params, 2, prefill_chunk=4, **kw)
    router = ReplicaRouter(reps, steal=True)
    rng = np.random.default_rng(5)
    long_toks = rng.integers(0, cfg.vocab_size, 14).astype(np.int32)
    short_toks = rng.integers(0, cfg.vocab_size, 2).astype(np.int32)
    # replica 0: fill both slots, then queue a fresh ticket with tid 2 —
    # its 2-token chunk lands in bucket 2, the long prompt's in bucket 4
    for i in range(2):
        reps[0].submit(Request(80 + i, short_toks.copy(), max_new_tokens=6))
    reps[0].step_once()
    collider_req = Request(1, short_toks.copy(), max_new_tokens=3)
    collider_t = reps[0].submit(collider_req)
    # replica 1: burn tids 0/1, then park the long prompt mid-prefill:
    # prefilling now holds a ticket whose tid is ALSO 2
    for i in range(2):
        reps[1].submit(Request(90 + i, short_toks.copy(), max_new_tokens=2))
    while reps[1].has_work:
        reps[1].step_once()
    long_req = Request(0, long_toks, max_new_tokens=3)
    long_t = reps[1].submit(long_req)
    reps[1].step_once()
    assert collider_t.tid == long_t.tid == 2
    assert len(reps[1].prefilling) == 1 and reps[1].free_slots == 1
    # replica 1 (no fresh queue, one free slot) steals the collider; the
    # resubmit/absorb append order then admits it in a bucket-2 group of
    # its own while the long prompt still owns its mid-prefill slot
    assert router.maybe_steal() == 1 and collider_t.stolen
    router.run_until_drained()
    assert long_req.done and collider_req.done
    # token identity against a fresh monolithic engine: slot corruption
    # from a tid-keyed prefilling map shows up as diverging outputs
    ref = InferenceEngine(cfg, params, **kw)
    ref_long = Request(0, long_toks.copy(), max_new_tokens=3)
    ref_short = Request(1, short_toks.copy(), max_new_tokens=3)
    ref.run([ref_long, ref_short])
    assert long_req.output == ref_long.output
    assert collider_req.output == ref_short.output


# ---- mixed-precision routing (quantized fleet) ----------------------------

def test_mixed_precision_pins_class0_to_fp32():
    """In a mixed fp32/int8 fleet, accuracy-sensitive (priority-0) traffic
    pins to the fp32 replica even when it is the MORE loaded one; bulk
    traffic keeps the plain min-load rule."""
    router = ReplicaRouter([_Stub(precision="fp32"),
                            _Stub(precision="w8a8")])
    assert router.mixed_precision
    assert router.summary()["precisions"] == ["fp32", "w8a8"]
    for i in range(3):                      # skew load onto the fp32 card
        router.replicas[0].submit(i)
    router.submit("high", priority=0)
    assert router.replicas[0].scheduler.depth == 4   # pinned despite load
    router.submit("bulk", priority=1)
    assert router.replicas[1].scheduler.depth == 1   # min-load for bulk
    assert router.fleet_telemetry().precision_rehomed == 0


def test_homogeneous_fleet_has_no_precision_pin():
    router = ReplicaRouter([_Stub(), _Stub()])
    assert not router.mixed_precision
    router.replicas[0].submit("x")
    router.submit("high", priority=0)
    assert router.replicas[1].scheduler.depth == 1   # plain min-load rule


def test_pin_degrades_when_last_fp32_dies_and_counts_rehome():
    """Graceful degradation: with the last fp32 replica fault-drained,
    class-0 work lands on int8 (served, not refused) and the downgrade is
    counted on the receiving replica's telemetry."""
    router = ReplicaRouter([_Stub(precision="fp32"),
                            _Stub(precision="w8a8")])
    router.drain_replica(0)
    t = router.submit("high", priority=0)
    assert not t.shed
    assert router.replicas[1].scheduler.depth == 1
    assert router.replicas[1].telemetry.precision_rehomed == 1
    assert router.fleet_telemetry().precision_rehomed == 1
    assert "precision_rehomed" in router.summary()
    assert "below their precision pin" in router.replicas[1].telemetry.report()


# ---- fleet telemetry aggregation (satellite: pooled percentiles) ----------

def test_fleet_percentiles_match_pooled_raw_samples():
    """Fleet p50/p95/p99 from Telemetry.merged must equal percentiles
    computed directly from the pooled per-replica raw samples."""
    rng = np.random.default_rng(42)
    parts, pooled = [], []
    for _ in range(3):
        t = Telemetry()
        samples = rng.lognormal(3.0, 1.0, rng.integers(5, 200)).tolist()
        for s in samples:
            t.record_latency(s, deadline_missed=bool(rng.integers(0, 2)))
        parts.append(t)
        pooled.extend(samples)
    fleet = Telemetry.merged(parts)
    got = fleet.latency_percentiles()
    ref = sorted(pooled)
    for p, key in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
        assert got[key] == percentile(ref, p)
    assert got["max"] == max(pooled)
    assert fleet.sla_total == sum(p.sla_total for p in parts)
    assert fleet.sla_misses == sum(p.sla_misses for p in parts)
    assert fleet.served == 0                # no served++ through record


def test_merged_counters_and_compiles_sum():
    a, b = Telemetry(), Telemetry()
    a.served, b.served = 3, 4
    a.record_compile("prefill"); b.record_compile("prefill")
    b.record_compile("decode")
    a.record_shed(); a.record_shed(); b.record_shed()
    a.serving_s, b.serving_s = 1.0, 2.5
    m = Telemetry.merged([a, b])
    assert m.served == 7
    assert m.compiles == {"prefill": 2, "decode": 1}
    assert m.shed == 3
    assert m.serving_s == 2.5               # slowest replica window
    assert "shed" in m.summary()


def test_merged_empty_is_empty():
    m = Telemetry.merged([])
    assert m.served == 0 and m.latencies_ms == []


def test_merged_round_trips_every_counter_field():
    """The "new counter forgotten in merge" regression guard: set EVERY
    Telemetry dataclass field nonzero by iterating the fields (not by
    naming them — a newly added counter is covered automatically) and
    check merged([t]) reproduces each one while merged([t, t]) sums the
    counters, pools the sample lists, and per-key-sums the dicts."""
    import dataclasses
    t = Telemetry()
    for i, f in enumerate(dataclasses.fields(Telemetry), start=1):
        if f.name == "wall_start":
            continue
        cur = getattr(t, f.name)
        if isinstance(cur, int):
            setattr(t, f.name, i)
        elif isinstance(cur, float):
            setattr(t, f.name, float(i))
        elif isinstance(cur, list):
            setattr(t, f.name, [i])
        elif isinstance(cur, dict):
            setattr(t, f.name, {"k": i})
        else:
            pytest.fail(f"unmergeable Telemetry field kind: {f.name}")
    m1, m2 = Telemetry.merged([t]), Telemetry.merged([t, t])
    for f in dataclasses.fields(Telemetry):
        if f.name == "wall_start":
            continue
        v = getattr(t, f.name)
        if f.name == "serving_s":           # fleet window = slowest replica
            assert getattr(m1, f.name) == v and getattr(m2, f.name) == v
        elif isinstance(v, int):
            assert getattr(m1, f.name) == v, f.name
            assert getattr(m2, f.name) == 2 * v, f.name
        elif isinstance(v, list):
            assert getattr(m1, f.name) == v and getattr(m2, f.name) == v + v
        elif isinstance(v, dict):
            assert getattr(m1, f.name) == v
            assert getattr(m2, f.name) == {"k": 2 * v["k"]}
    # the PR 4 counters specifically must reach the JSON surface
    s = m2.summary()
    assert s["steals"] == 2 * t.steals and s["drained"] == 2 * t.drained


def test_reset_clears_new_counters_but_keeps_compiles():
    t = Telemetry()
    t.record_steal(3)
    t.record_drained(2)
    t.record_compile("prefill")
    t.reset_serving_stats()
    assert t.steals == 0 and t.drained == 0
    assert t.compiles == {"prefill": 1}      # executables are engine state


# ---- LM engines behind the router ----------------------------------------

@pytest.fixture(scope="module")
def lm_setup():
    cfg = reduce_for_smoke(get_config("deepseek-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _trace(cfg, n=8, slo_ms=None, prios=None):
    rng = np.random.default_rng(11)
    lens = (4, 6, 5, 7, 3, 6, 4, 5)
    return [Request(i, rng.integers(0, cfg.vocab_size, l).astype(np.int32),
                    max_new_tokens=3, slo_ms=slo_ms,
                    priority=0 if prios is None else prios[i])
            for i, l in enumerate(lens[:n])]


def test_two_replica_lm_run(lm_setup):
    cfg, params = lm_setup
    reps = make_replicas(cfg, params, 2, batch_slots=2, max_len=32,
                         prefill_buckets=(8, 16))
    router = ReplicaRouter(reps)
    reqs = _trace(cfg)
    for r in reqs:
        router.submit(r, slo_ms=60_000.0)
    assert spread(router) <= 1
    router.run_until_drained()
    fleet = router.fleet_telemetry()
    assert fleet.served == len(reqs)
    assert all(r.done for r in reqs)
    assert fleet.sla_total == len(reqs) and fleet.sla_misses == 0
    s = router.summary()
    assert s["replicas"] == 2 and sum(s["routed_per_replica"]) == len(reqs)


def test_run_concurrent_rebases_per_replica_timelines(lm_setup):
    """Sequentially-drained replicas must not charge each other's drain
    time: with 2 replicas each serving half the trace, every request's
    latency stays near the single-replica scale instead of growing by a
    whole replica-drain."""
    cfg, params = lm_setup
    reps = make_replicas(cfg, params, 2, batch_slots=2, max_len=32,
                         prefill_buckets=(8, 16))
    router = ReplicaRouter(reps)
    for r in _trace(cfg):
        router.submit(r)
    router.run_concurrent()
    # after rebasing, a request's latency cannot exceed its own replica's
    # drain window (plus stamping slack); without the rebase, replica 1's
    # latencies would carry replica 0's whole window on top
    for rep in reps:
        assert max(rep.telemetry.latencies_ms) \
            <= rep.telemetry.serving_s * 1e3 + 5.0
    assert router.fleet_telemetry().served == 8


def test_run_concurrent_refuses_inflight_fleet(lm_setup):
    cfg, params = lm_setup
    reps = make_replicas(cfg, params, 1, batch_slots=2, max_len=32,
                         prefill_buckets=(8,))
    router = ReplicaRouter(reps)
    for r in _trace(cfg, n=4):
        router.submit(r)
    reps[0].step_once()                     # now in flight
    with pytest.raises(RuntimeError):
        router.run_concurrent()
    router.run_until_drained()              # still drainable the live way


def test_priority_and_shedding_through_lm_engine(lm_setup):
    """Overload isolation end-to-end: strict-priority admission serves
    class 0 first and the feasibility check sheds only class-1 traffic;
    shed requests consume no prefill/decode dispatches."""
    cfg, params = lm_setup
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=32,
                          prefill_buckets=(8,), policy="priority",
                          service_ms_est=50.0)
    prios = [1, 1, 0, 1, 1, 0, 1, 1]
    reqs = _trace(cfg, prios=prios)
    for r, p in zip(reqs, prios):
        # class 0: generous slo; class 1: infeasible once 2 are ahead
        r.slo_ms = 60_000.0 if p == 0 else 150.0
    tickets = [eng.submit(r) for r in reqs]
    assert not any(t.shed for t, p in zip(tickets, prios) if p == 0)
    assert any(t.shed for t, p in zip(tickets, prios) if p == 1)
    dispatches_before = dict(eng.telemetry.stage_calls)
    assert dispatches_before == {}          # nothing ran at submit time
    while eng.has_work:
        eng.step_once()
    served = [r for r, t in zip(reqs, tickets) if not t.shed]
    assert all(r.done for r in served)
    assert eng.telemetry.served == len(served)
    assert eng.telemetry.prefills == len(served)   # shed never prefilled
    assert eng.telemetry.shed == sum(t.shed for t in tickets)
