"""Property-based scheduler-policy invariants (hypothesis; degrades to
the deterministic conftest shim when the package is missing).

Every policy must state and test its invariants before it ships — the
contract this suite pins down (see ROADMAP.md "Testing strategy"):

- conservation: across any submit/admit/complete interleaving no ticket
  is lost or duplicated — submitted == admitted + pending + shed,
- FIFO admits in arrival order,
- EDF never inverts deadlines within an admitted batch, and never leaves
  a strictly-earlier deadline waiting behind an admitted one,
- size x time batches are bucket-coherent,
- priority+aging guarantees bounded starvation (a priority-p ticket
  outranks any fresh priority-0 ticket after waiting p * aging_s),
- shed tickets never reach admit (so they can never consume an executor
  dispatch) and count only in the rejection counter,
- admission sequences are deterministic under a fixed seed,
- the router always lands a submit on a minimum-load replica, so the
  routed-count spread over an all-submit sequence is bounded by 1,
- chunked-prefill continuations (PR 3): conservation holds with
  continuation tickets in flight (submitted = finally-admitted +
  pending + shed, resubmits counted separately), a continuation never
  loses priority/aging credit or its deadline, coherent-group admission
  is bucket-pure and respects the fresh-ticket slot cap, and chunked
  admission is deterministic under a fixed seed,
- cross-replica work stealing + fault drain (PR 4, via the
  deterministic fleet sim in fleet_sim.py): fleet-wide conservation
  under arbitrary submit/steal/fail/complete interleavings (submitted =
  completed + pending-anywhere + shed, no duplication across queues), a
  stolen ticket keeps its tid/priority/deadline and aging credit — and
  is never a continuation, stealing is deterministic under a fixed
  seed, and drain_replica re-homes every pending ticket exactly once.

All tests drive the scheduler on a virtual clock (the ``now=`` hooks), so
they are exact — no wall-clock tolerance anywhere.
"""
from collections import Counter

import numpy as np
import pytest
from hypothesis import assume, given, note, settings
from hypothesis import strategies as st

from repro.core.bucketing import pick_bucket
from repro.serving.router import ReplicaRouter, spread
from repro.serving.scheduler import (NO_SLO, PriorityAgingPolicy, Scheduler,
                                     SizeTimePolicy)
from repro.serving.telemetry import Telemetry

POLICY_NAMES = ("fifo", "edf", "sizetime", "priority")


def _random_trace(rng, n):
    """(size, priority, slo_ms-or-None) per ticket plus arrival times."""
    sizes = rng.integers(1, 300, n)
    prios = rng.integers(0, 3, n)
    slos = [None if rng.random() < 0.3 else float(rng.uniform(5, 500))
            for _ in range(n)]
    arrivals = np.cumsum(rng.uniform(0.0, 0.01, n))
    return sizes, prios, slos, arrivals


# ---- conservation ---------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 40),
       policy=st.sampled_from(POLICY_NAMES),
       max_queue=st.integers(0, 1), k=st.integers(1, 6))
def test_no_ticket_lost_or_duplicated(seed, n, policy, max_queue, k):
    """Multiset identity over any interleaving: every submitted tid ends
    up exactly once in {admitted, still-pending, shed}."""
    rng = np.random.default_rng(seed)
    s = Scheduler(policy, max_queue=n // 2 if max_queue else None,
                  service_ms_est=None)
    sizes, prios, slos, arrivals = _random_trace(rng, n)
    submitted, admitted, shed = [], [], []
    now = 0.0
    for i in range(n):
        now = float(arrivals[i])
        t = s.submit(i, size=int(sizes[i]), priority=int(prios[i]),
                     slo_ms=slos[i], now=now)
        submitted.append(t)
        if t.shed:
            shed.append(t)
        if rng.random() < 0.4:                  # interleave admissions
            got = s.admit(k, now=now)
            admitted.extend(got)
            for g in got:
                s.complete(g, now=now + 0.001)
    while s.depth:                              # drain
        admitted.extend(s.admit(k, now=now))
    tids = Counter(t.tid for t in admitted) \
        + Counter(t.tid for t in shed)
    assert set(tids) == {t.tid for t in submitted}
    assert all(c == 1 for c in tids.values()), "ticket duplicated"
    assert len(admitted) + len(shed) == n


# ---- per-policy ordering invariants --------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 30),
       k=st.integers(1, 8))
def test_fifo_admits_in_arrival_order(seed, n, k):
    rng = np.random.default_rng(seed)
    s = Scheduler("fifo")
    _, _, slos, arrivals = _random_trace(rng, n)
    for i in range(n):
        s.submit(i, slo_ms=slos[i], now=float(arrivals[i]))
    prev = -1
    while s.depth:
        for t in s.admit(k, now=99.0):
            assert t.payload > prev, "FIFO inversion"
            prev = t.payload


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 30),
       k=st.integers(1, 8))
def test_edf_never_inverts_deadlines(seed, n, k):
    """Within one admitted batch deadlines are non-decreasing, and no
    ticket left pending has a strictly earlier deadline than any ticket
    in the batch (deadline-less tickets sort last)."""
    rng = np.random.default_rng(seed)
    s = Scheduler("edf")
    _, _, slos, arrivals = _random_trace(rng, n)
    for i in range(n):
        s.submit(i, slo_ms=slos[i], now=float(arrivals[i]))
    while s.depth:
        batch = s.admit(k, now=99.0)
        dls = [t.deadline_t if t.deadline_t is not None else float("inf")
               for t in batch]
        assert dls == sorted(dls), "EDF inverted deadlines within a batch"
        if s.depth:
            left = min(t.deadline_t if t.deadline_t is not None
                       else float("inf") for t in s._pending)
            assert left >= dls[-1]    # inf >= inf holds for best-effort


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 30),
       k=st.integers(1, 8))
def test_sizetime_batches_are_bucket_coherent(seed, n, k):
    buckets = (32, 64, 128, 256)
    rng = np.random.default_rng(seed)
    s = Scheduler(SizeTimePolicy(buckets))
    sizes, _, _, arrivals = _random_trace(rng, n)
    for i in range(n):
        s.submit(i, size=int(sizes[i]), now=float(arrivals[i]))
    while s.depth:
        batch = s.admit(k, now=99.0)
        got = {pick_bucket(t.size, buckets) for t in batch}
        assert len(got) == 1, f"size x time batch spans buckets {got}"


# ---- priority + aging -----------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), prio=st.integers(1, 4),
       aging_s=st.floats(0.1, 5.0))
def test_priority_aging_rank_bound(seed, prio, aging_s):
    """The documented starvation bound: once a priority-p ticket has
    waited more than p * aging_s, it outranks ANY freshly-arrived
    priority-0 ticket."""
    pol = PriorityAgingPolicy(aging_s=aging_s)
    s = Scheduler(pol)
    old = s.submit("old", priority=prio, now=0.0)
    now = prio * aging_s * 1.001            # just past the bound
    s.submit("fresh", priority=0, now=now)
    assert pol.rank(old, now) < 0.0
    assert [t.payload for t in s.admit(1, now=now)] == ["old"]


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), prio=st.integers(1, 3))
def test_priority_aging_bounded_starvation_under_stream(seed, prio):
    """A low-priority ticket competing against an endless stream of fresh
    priority-0 arrivals (one per round, one admission per round) is
    admitted within prio * aging_s / dt + backlog + 1 rounds — it cannot
    starve."""
    aging_s, dt = 0.5, 0.1
    rng = np.random.default_rng(seed)
    s = Scheduler(PriorityAgingPolicy(aging_s=aging_s))
    victim = s.submit("victim", priority=prio, now=0.0)
    bound = int(prio * aging_s / dt) + 2
    for round_i in range(bound + 1):
        now = (round_i + 1) * dt
        s.submit(f"fresh{round_i}", priority=0, now=now)
        got = s.admit(1, now=now)
        if any(t.tid == victim.tid for t in got):
            assert round_i <= bound
            return
    pytest.fail(f"priority-{prio} ticket starved past the "
                f"{bound}-round bound")


# ---- shedding -------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 40),
       policy=st.sampled_from(POLICY_NAMES))
def test_shed_tickets_never_reach_admit(seed, n, policy):
    """Shed tickets are never admitted (so they can never consume an
    executor dispatch), count only in telemetry.shed, and leave SLA
    accounting untouched."""
    rng = np.random.default_rng(seed)
    tel = Telemetry()
    s = Scheduler(policy, telemetry=tel, max_queue=3, service_ms_est=10.0)
    sizes, prios, slos, arrivals = _random_trace(rng, n)
    shed_tids, admitted = set(), []
    for i in range(n):
        t = s.submit(i, size=int(sizes[i]), priority=int(prios[i]),
                     slo_ms=slos[i], now=float(arrivals[i]))
        if t.shed:
            shed_tids.add(t.tid)
        if rng.random() < 0.3:
            admitted.extend(s.admit(2, now=float(arrivals[i])))
    while s.depth:
        admitted.extend(s.admit(4, now=99.0))
    assert not (shed_tids & {t.tid for t in admitted})
    assert tel.shed == len(shed_tids)
    assert tel.sla_total == 0               # nothing completed yet
    for t in admitted:
        s.complete(t, now=100.0)
    # completions count toward SLA, sheds still only in the shed counter
    assert tel.sla_total == sum(1 for t in admitted
                                if t.deadline_t is not None)
    assert tel.shed == len(shed_tids)


# ---- determinism ----------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 30),
       policy=st.sampled_from(POLICY_NAMES))
def test_admission_deterministic_under_fixed_seed(seed, n, policy):
    """Same trace + same virtual clock => identical admission order."""
    def run():
        rng = np.random.default_rng(seed)
        s = Scheduler(policy, max_queue=n // 2 + 1, service_ms_est=5.0)
        sizes, prios, slos, arrivals = _random_trace(rng, n)
        order = []
        for i in range(n):
            s.submit(i, size=int(sizes[i]), priority=int(prios[i]),
                     slo_ms=slos[i], now=float(arrivals[i]))
            if rng.random() < 0.5:
                order.extend(t.tid for t in s.admit(2,
                                                    now=float(arrivals[i])))
        while s.depth:
            order.extend(t.tid for t in s.admit(3, now=99.0))
        return order

    assert run() == run()


# ---- SLA boundary semantics (regression pin, satellite) -------------------

def test_sla_boundary_exactly_at_deadline_is_a_hit():
    """Pin the boundary the router relies on: finishing exactly AT the
    deadline is a hit; any time past it is a miss; shed tickets appear
    only in the rejection counter, never in misses or latencies."""
    tel = Telemetry()
    s = Scheduler("fifo", telemetry=tel, default_slo_ms=100.0, max_queue=2)
    at = s.submit("at", now=0.0)        # deadline_t = 0.1
    past = s.submit("past", now=0.0)
    shed = s.submit("overflow", now=0.0)
    assert shed.shed and tel.shed == 1
    s.admit(2, now=0.0)
    s.complete(at, now=0.1)             # exactly at the deadline
    s.complete(past, now=0.1 + 1e-6)    # one tick past it
    assert tel.sla_total == 2
    assert tel.sla_misses == 1
    assert len(tel.latencies_ms) == 2   # shed never produced a latency
    assert tel.shed == 1


def test_best_effort_no_slo_never_counts():
    tel = Telemetry()
    s = Scheduler("fifo", telemetry=tel, default_slo_ms=50.0)
    t = s.submit("be", slo_ms=NO_SLO, now=0.0)
    s.admit(1, now=0.0)
    s.complete(t, now=9.0)
    assert tel.sla_total == 0 and tel.sla_misses == 0
    assert tel.served == 1


# ---- chunked-prefill continuations (PR 3) --------------------------------

def _buckets_fn(buckets=(8, 16, 32)):
    return lambda t: pick_bucket(max(t.size, 1), buckets)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 30),
       policy=st.sampled_from(POLICY_NAMES), k=st.integers(1, 4))
def test_conservation_with_continuations_in_flight(seed, n, policy, k):
    """Multiset identity with chunking: a ticket admitted mid-prefill
    re-enters the queue via resubmit; across any interleaving every
    submitted tid still ends up exactly once in {finally-admitted,
    pending, shed}, and the continuation counter equals the number of
    resubmits — no ticket is lost, duplicated, or shed mid-flight."""
    rng = np.random.default_rng(seed)
    tel = Telemetry()
    s = Scheduler(policy, telemetry=tel, max_queue=n)
    sizes, prios, slos, arrivals = _random_trace(rng, n)
    chunks_left = {}                    # tid -> remaining chunks
    submitted, done, shed = [], [], []
    resubmits = 0
    now = 0.0
    for i in range(n):
        now = float(arrivals[i])
        t = s.submit(i, size=int(sizes[i]), priority=int(prios[i]),
                     slo_ms=slos[i], now=now)
        submitted.append(t)
        if t.shed:
            shed.append(t)
        else:
            chunks_left[t.tid] = int(rng.integers(1, 4))
        if rng.random() < 0.5:
            got = s.admit_coherent(k, now=now, bucket_fn=_buckets_fn(),
                                   new_cap=k)
            for g in got:
                chunks_left[g.tid] -= 1
                if chunks_left[g.tid] > 0:
                    s.resubmit(g, size=max(g.size // 2, 1), now=now)
                    resubmits += 1
                else:
                    done.append(g)
    while s.depth:                      # drain, one chunk per round
        now += 0.01
        for g in s.admit_coherent(k, now=now, bucket_fn=_buckets_fn(),
                                  new_cap=k):
            chunks_left[g.tid] -= 1
            if chunks_left[g.tid] > 0:
                s.resubmit(g, size=max(g.size // 2, 1), now=now)
                resubmits += 1
            else:
                done.append(g)
    tids = Counter(t.tid for t in done) + Counter(t.tid for t in shed)
    assert set(tids) == {t.tid for t in submitted}
    assert all(c == 1 for c in tids.values()), "ticket duplicated"
    assert tel.continuations == resubmits
    assert tel.shed == len(shed)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), prio=st.integers(1, 4),
       aging_s=st.floats(0.1, 5.0))
def test_continuation_keeps_priority_and_aging_credit(seed, prio, aging_s):
    """A continuation preserves tid, enqueue_t, priority, and deadline:
    once the original ticket has waited past prio * aging_s, its
    continuation outranks a freshly-arrived priority-0 ticket exactly
    as the original would have — chunking cannot reset the
    bounded-starvation clock."""
    pol = PriorityAgingPolicy(aging_s=aging_s)
    s = Scheduler(pol, default_slo_ms=500.0)
    old = s.submit("old", priority=prio, now=0.0)
    deadline = old.deadline_t
    got = s.admit(1, now=0.1)
    assert got == [old]
    s.resubmit(old, size=7, now=0.2)
    assert old.continuation and old.size == 7
    assert old.enqueue_t == 0.0                 # aging credit preserved
    assert old.deadline_t == deadline           # EDF rank preserved
    now = prio * aging_s * 1.001                # just past the bound
    s.submit("fresh", priority=0, now=now)
    assert [t.payload for t in s.admit(1, now=now)] == ["old"]


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 30),
       k=st.integers(1, 6), cap=st.integers(0, 3))
def test_admit_coherent_is_bucket_pure_and_caps_fresh(seed, n, k, cap):
    """Every coherent group maps to ONE bucket, and at most new_cap of
    its members are fresh (continuations already own a KV slot, fresh
    tickets need a free one)."""
    rng = np.random.default_rng(seed)
    s = Scheduler("fifo")
    bucket_fn = _buckets_fn()
    for i in range(n):
        t = s.submit(i, size=int(rng.integers(1, 40)),
                     now=float(i) * 0.01)
        if rng.random() < 0.3:          # some tickets are continuations
            s.admit(0)                  # no-op, keeps clock semantics
            t.continuation = True
            s.telemetry.record_continuation()
    while s.depth:
        before = s.depth
        group = s.admit_coherent(k, now=99.0, bucket_fn=bucket_fn,
                                 new_cap=cap)
        if not group:
            # only fresh tickets left and cap == 0: nothing admissible
            assert cap == 0
            assert not any(t.continuation for t in s._pending)
            break
        assert len(group) <= k
        assert len({bucket_fn(t) for t in group}) == 1, "bucket impure"
        assert sum(not t.continuation for t in group) <= cap
        assert s.depth == before - len(group)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 30),
       policy=st.sampled_from(POLICY_NAMES))
def test_chunked_admission_deterministic_under_fixed_seed(seed, n, policy):
    """Same trace + same virtual clock => identical coherent-admission
    order, including the resubmit interleavings."""
    def run():
        rng = np.random.default_rng(seed)
        s = Scheduler(policy)
        sizes, prios, slos, arrivals = _random_trace(rng, n)
        order = []
        chunks = {}
        for i in range(n):
            t = s.submit(i, size=int(sizes[i]), priority=int(prios[i]),
                         slo_ms=slos[i], now=float(arrivals[i]))
            chunks[t.tid] = int(rng.integers(1, 3))
            if rng.random() < 0.5:
                for g in s.admit_coherent(2, now=float(arrivals[i]),
                                          bucket_fn=_buckets_fn(),
                                          new_cap=2):
                    order.append(g.tid)
                    chunks[g.tid] -= 1
                    if chunks[g.tid] > 0:
                        s.resubmit(g, now=float(arrivals[i]))
        now = 99.0
        while s.depth:
            now += 0.01
            for g in s.admit_coherent(3, now=now, bucket_fn=_buckets_fn(),
                                      new_cap=3):
                order.append(g.tid)
                chunks[g.tid] -= 1
                if chunks[g.tid] > 0:
                    s.resubmit(g, now=now)
        return order

    assert run() == run()


def test_resubmit_refuses_shed_ticket():
    s = Scheduler("fifo", max_queue=0)
    t = s.submit("x", now=0.0)
    assert t.shed
    with pytest.raises(ValueError):
        s.resubmit(t)


# ---- live service estimation (auto admission calibration) -----------------

def test_auto_estimator_falls_back_until_samples_exist():
    """service_ms_est="auto": static fallback until min_samples, then
    the per-bucket p50 of observed admit->finish service times. A bucket
    with no samples of its own borrows the pooled p50 SIZE-RESCALED from
    the median sampled bucket (PR 9) — the old raw pooled borrow priced
    a 512-token prefill off a 32-token sample set."""
    s = Scheduler("fifo", service_ms_est="auto", service_ms_fallback=20.0)
    assert s.service_ms_for(10) == 20.0          # fallback seeds the check
    for i in range(5):
        t = s.submit(i, size=10, now=float(i))
        s.admit(1, now=float(i))
        s.complete(t, now=float(i) + 0.05)       # 50 ms service each
    assert s.service_ms_for(10) == pytest.approx(50.0)
    # a cold bucket borrows the pooled p50 rescaled from the anchor
    # bucket (32, where every sample lives) to its own size: with no
    # perf model wired the prior is linear, 50ms * 512/32
    assert s.service_ms_for(400) == pytest.approx(50.0 * 512 / 32)


def test_auto_estimator_none_without_fallback_means_no_shedding():
    s = Scheduler("fifo", service_ms_est="auto", default_slo_ms=0.001)
    t = s.submit("tight", now=0.0)               # absurdly tight deadline
    assert not t.shed                            # no estimate -> no check


def test_auto_estimator_sheds_like_static_once_calibrated():
    """Once calibrated, the feasibility check sheds a ticket whose slack
    cannot cover the queue ahead at the measured per-bucket p50."""
    s = Scheduler("fifo", service_ms_est="auto")
    for i in range(5):
        t = s.submit(i, size=8, now=float(i))
        s.admit(1, now=float(i))
        s.complete(t, now=float(i) + 0.1)        # 100 ms per ticket
    for i in range(3):                           # 3 pending ahead
        s.submit(f"p{i}", size=8, now=10.0)
    ok = s.submit("roomy", size=8, slo_ms=1_000.0, now=10.0)
    tight = s.submit("tight", size=8, slo_ms=150.0, now=10.0)
    assert not ok.shed
    assert tight.shed                            # needs ~500ms, has 150
    assert s.service_ms_for(8) == pytest.approx(100.0)


def test_rejects_unknown_service_est_string():
    with pytest.raises(ValueError):
        Scheduler("fifo", service_ms_est="fast")


# ---- router balance -------------------------------------------------------

from conftest import StubReplica as _StubReplica  # noqa: E402


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n_replicas=st.integers(2, 6),
       n=st.integers(1, 60))
def test_router_pure_submit_spread_bounded_by_one(seed, n_replicas, n):
    """From an empty fleet, any all-submit sequence lands every ticket on
    a current-minimum replica, so the routed-count spread never exceeds
    1 — the provable balance bound."""
    router = ReplicaRouter([_StubReplica() for _ in range(n_replicas)])
    rng = np.random.default_rng(seed)
    for i in range(n):
        router.submit(i, slo_ms=float(rng.uniform(10, 100))
                      if rng.random() < 0.5 else None)
        assert spread(router) <= 1
        assert router.shed == 0


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n_replicas=st.integers(2, 5),
       n=st.integers(1, 60))
def test_router_always_picks_a_min_load_replica(seed, n_replicas, n):
    """Even with random draining interleaved, every submit lands on a
    replica whose load was minimal at that instant."""
    router = ReplicaRouter([_StubReplica() for _ in range(n_replicas)])
    rng = np.random.default_rng(seed)
    for i in range(n):
        if rng.random() < 0.4:          # drain a random replica a bit
            r = router.replicas[int(rng.integers(n_replicas))]
            if r.has_work:
                r.step_once()
        loads = [router.load(j) for j in range(n_replicas)]
        before = list(router.routed)
        router.submit(i)
        j = next(j for j in range(n_replicas)
                 if router.routed[j] != before[j])
        assert loads[j] == min(loads), \
            f"routed to load {loads[j]}, min was {min(loads)}"


# ---- per-slot sequence state (PR 5) ---------------------------------------

from repro.serving.state import (SequenceStateManager,  # noqa: E402
                                 require_chunkable)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), slots=st.integers(1, 6),
       n_ops=st.integers(1, 120))
def test_sequence_state_partition_invariant(seed, slots, n_ops):
    """Slot conservation through ANY lifecycle interleaving: at every
    instant the slots partition into exactly free | active | prefilling
    (pairwise disjoint, union = all slots), a parked ticket gets ITS OWN
    slot back on re-acquire, and evict_all returns every slot-holding
    ticket exactly once and resets to all-free."""
    from repro.serving.scheduler import Ticket
    rng = np.random.default_rng(seed)
    mgr = SequenceStateManager(slots)
    held = {}                      # id(ticket) -> (ticket, slot, state)
    next_tid = 0
    for _ in range(n_ops):
        op = rng.integers(0, 5)
        if op == 0 and mgr.free_count:              # fresh acquire
            t = Ticket(next_tid, None)
            next_tid += 1
            s = mgr.acquire(t)
            if rng.random() < 0.5:
                mgr.activate(t, s, int(rng.integers(1, 64)))
                held[id(t)] = (t, s, "active")
            else:
                mgr.park(t, s)
                held[id(t)] = (t, s, "prefilling")
        elif op == 1:                               # continuation chunk
            parked = [(t, s) for t, s, st_ in held.values()
                      if st_ == "prefilling"]
            if parked:
                t, s = parked[int(rng.integers(len(parked)))]
                got = mgr.acquire(t)
                assert got == s, "continuation lost its own slot"
                mgr.activate(t, got, int(rng.integers(1, 64)))
                held[id(t)] = (t, got, "active")
        elif op == 2:                               # completion
            act = [(t, s) for t, s, st_ in held.values() if st_ == "active"]
            if act:
                t, s = act[int(rng.integers(len(act)))]
                mgr.release(s)
                del held[id(t)]
        elif op == 3:                               # steal-veto spot check
            t = Ticket(next_tid, None)
            next_tid += 1
            assert mgr.steal_eligible(t)            # fresh: stealable
            for ht, hs, st_ in held.values():
                if st_ == "prefilling":
                    assert not mgr.steal_eligible(ht)
        else:                                       # fault drain
            evicted = mgr.evict_all()
            active_held = [t for t, _, st_ in held.values()
                           if st_ == "active"]
            assert sorted(id(t) for t in evicted) \
                == sorted(id(t) for t in active_held)
            assert mgr.free_count == slots and mgr.inflight == 0
            held.clear()
        mgr.check_partition()
        assert mgr.inflight == len(held)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), slots=st.integers(1, 6),
       n_ops=st.integers(1, 120))
def test_partition_invariant_with_paging_and_migration(seed, slots, n_ops):
    """PR 8 partition moves compose with the PR 5 lifecycle: random
    interleavings of acquire / park / activate / release with
    ``page_out`` (active -> free, ticket leaves to the engine's paged
    store), its fault-back re-``acquire`` + ``activate`` at the parked
    position, and ``release_prefilling`` (migration-out: prefilling ->
    free) keep the partition exact at every step — and after
    ``evict_all`` a restore of the evicted sessions rebuilds the exact
    free/active/prefilling split."""
    from repro.serving.scheduler import Ticket
    rng = np.random.default_rng(seed)
    mgr = SequenceStateManager(slots)
    held = {}                     # id(ticket) -> (ticket, slot, state)
    paged = []                    # (ticket, pos) — engine-side paged store
    next_tid = 0
    for _ in range(n_ops):
        op = rng.integers(0, 7)
        if op == 0 and mgr.free_count:              # fresh acquire
            t = Ticket(next_tid, None)
            next_tid += 1
            s = mgr.acquire(t)
            if rng.random() < 0.5:
                mgr.activate(t, s, int(rng.integers(1, 64)))
                held[id(t)] = (t, s, "active")
            else:
                mgr.park(t, s)
                held[id(t)] = (t, s, "prefilling")
        elif op == 1:                               # continuation chunk
            parked = [(t, s) for t, s, st_ in held.values()
                      if st_ == "prefilling"]
            if parked:
                t, s = parked[int(rng.integers(len(parked)))]
                assert mgr.acquire(t) == s
                mgr.activate(t, s, int(rng.integers(1, 64)))
                held[id(t)] = (t, s, "active")
        elif op == 2:                               # completion
            act = [(t, s) for t, s, st_ in held.values() if st_ == "active"]
            if act:
                t, s = act[int(rng.integers(len(act)))]
                mgr.release(s)
                del held[id(t)]
        elif op == 3:                               # page-out (PR 8)
            act = [(t, s) for t, s, st_ in held.values() if st_ == "active"]
            if act:
                t, s = act[int(rng.integers(len(act)))]
                pos = int(mgr.pos[s])
                got = mgr.page_out(s)
                assert got is t, "page_out returned the wrong ticket"
                paged.append((t, pos))
                del held[id(t)]
        elif op == 4 and paged and mgr.free_count:  # fault-back (PR 8)
            t, pos = paged.pop(0)
            s = mgr.acquire(t)
            mgr.activate(t, s, pos)
            assert int(mgr.pos[s]) == pos           # resumes where parked
            held[id(t)] = (t, s, "active")
        elif op == 5:                               # migration-out (PR 8)
            parked = [(t, s) for t, s, st_ in held.values()
                      if st_ == "prefilling"]
            if parked:
                t, s = parked[int(rng.integers(len(parked)))]
                assert mgr.release_prefilling(t) == s
                del held[id(t)]                     # left with its snapshot
        else:                                       # evict + exact restore
            evicted = mgr.evict_all()
            assert sorted(id(t) for t in evicted) == sorted(
                id(t) for t, _, st_ in held.values() if st_ == "active")
            assert mgr.free_count == slots and mgr.inflight == 0
            # restore every evicted session into fresh slots: the
            # partition must come back exactly as large as before
            restored = 0
            for t in evicted:
                if not mgr.free_count:
                    break
                s = mgr.acquire(t)
                mgr.activate(t, s, int(rng.integers(1, 64)))
                restored += 1
            held = {id(t): (t, s, "active")
                    for s, t in mgr.active.items()}
            assert len(held) == restored == len(evicted)
        mgr.check_partition()
        assert mgr.inflight == len(held)
        # a paged ticket holds NO slot: it must be invisible to the
        # partition and fresh-stealable only via the engine's veto,
        # not the manager's
        for t, _ in paged:
            assert id(t) not in mgr.prefilling


def test_require_chunkable_names_offending_kind():
    """The capability check replacing the all-global gate: every
    state-carrying kind passes; encoder-decoder raises naming the
    cross-attention decoder kind."""
    from repro.configs import get_config, reduce_for_smoke
    for arch in ("deepseek-7b", "gemma2-27b", "mamba2-130m",
                 "recurrentgemma-9b"):
        require_chunkable(reduce_for_smoke(get_config(arch)))  # no raise
    with pytest.raises(ValueError, match="decoder"):
        require_chunkable(reduce_for_smoke(get_config("whisper-medium")))


# ---- cross-replica work stealing + fault drain (PR 4) ---------------------

from fleet_sim import FleetSim, random_schedule, run_to_completion  # noqa: E402


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_replicas=st.integers(2, 4),
       n_ops=st.integers(5, 120), steal=st.booleans(), fail=st.booleans(),
       policy=st.sampled_from(POLICY_NAMES))
def test_fleet_conservation_under_steal_and_fault(seed, n_replicas, n_ops,
                                                  steal, fail, policy):
    """Fleet-wide multiset identity through ANY seeded interleaving of
    submit (hot-keyed skew), virtual ticks, stealing rounds, and a
    mid-run replica kill: submitted = completed + pending-anywhere +
    shed, with no ticket duplicated across queues — and after the drain
    every accepted ticket still completes."""
    sim = FleetSim(replicas=n_replicas, seed=seed, steal=steal,
                   policy=policy, slots=1 + seed % 2,
                   service_s=[0.004 * (1 + i) for i in range(n_replicas)],
                   max_queue=12)
    failed = random_schedule(sim, n_ops, skew=0.5, hot=0, max_priority=2,
                             fail_at=n_ops // 2 if fail else -1)
    run_to_completion(sim)
    note(f"failed={failed} shed={len(sim.shed)} "
         f"steals={sum(sim.router.steals_per_replica)}")
    sim.assert_conserved()
    assert len(sim.completed) == sum(1 for t in sim.submitted if not t.shed)
    if failed >= 0:
        assert not sim.replicas[failed].has_work


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_replicas=st.integers(2, 4),
       n_ops=st.integers(5, 120), steal=st.booleans(), fail=st.booleans(),
       policy=st.sampled_from(POLICY_NAMES))
def test_fleet_conservation_under_paging_and_migration(seed, n_replicas,
                                                       n_ops, steal, fail,
                                                       policy):
    """PR 8 acceptance: the conservation identity survives random
    page-out / page-in / migrate events interleaved with submits, ticks,
    steal rounds, and a mid-run kill — submitted = completed +
    pending-anywhere + shed, nothing duplicated, paged sessions included
    in pending — and every accepted ticket still completes after the
    drain (a paged or migrated session is never stranded)."""
    sim = FleetSim(replicas=n_replicas, seed=seed, steal=steal,
                   policy=policy, slots=2 + seed % 2,
                   service_s=[0.004 * (1 + i) for i in range(n_replicas)],
                   max_queue=12)
    failed = random_schedule(sim, n_ops, skew=0.5, hot=0, max_priority=2,
                             fail_at=n_ops // 2 if fail else -1,
                             p_page=0.3, p_migrate=0.2)
    run_to_completion(sim)
    tel = sim.router.fleet_telemetry()
    note(f"failed={failed} shed={len(sim.shed)} paged_out={tel.paged_out} "
         f"migrated={tel.migrated}")
    sim.assert_conserved()
    assert len(sim.completed) == sum(1 for t in sim.submitted if not t.shed)
    # every fault-back had a park; the shortfall is sessions that
    # completed-by-drain or died with a failed card while still paged
    assert tel.paged_in <= tel.paged_out
    if failed >= 0:
        assert not sim.replicas[failed].has_work


def test_migrated_ticket_keeps_credit_and_remaining_service():
    """Sim-level migration contract: the moved ticket keeps tid /
    priority / deadline untouched (shared virtual clock — no restamp),
    its frozen remaining service resumes on the destination (no
    restart-from-zero), and the move lands in ``migrated``, not
    ``steals``."""
    sim = FleetSim(replicas=2, seed=0, steal=False, slots=1,
                   service_s=0.01, dt=0.005)
    t = sim.submit(size=4, priority=3, slo_ms=500.0, pin=0)
    tid, prio, deadline = t.tid, t.priority, t.deadline_t
    sim.tick()                                  # admit: due at now+0.01
    (tkt, due), = sim.replicas[0].active
    assert tkt is t
    moved = sim.migrate(0, 1)
    assert moved == 1
    assert not sim.replicas[0].active
    (tkt2, due2), = sim.replicas[1].active
    assert tkt2 is t
    assert t.tid == tid and t.priority == prio and t.deadline_t == deadline
    # frozen remaining service: the due time is preserved exactly
    # (migrate() re-bases from now, and now hasn't advanced)
    assert due2 == pytest.approx(due)
    tel = sim.router.fleet_telemetry()
    assert tel.migrated == 1 and tel.steals == 0
    run_to_completion(sim)
    sim.assert_conserved()
    assert t in sim.completed


def test_page_out_round_trip_preserves_remaining_service():
    """A page-out/page-in round trip at the sim level loses no progress:
    remaining service is frozen while parked and resumes exactly."""
    sim = FleetSim(replicas=1, seed=0, steal=False, slots=1,
                   service_s=0.1, dt=0.005)
    t = sim.submit(size=4, pin=0)
    sim.tick()                                  # due at 0.005 + 0.1
    (_, due), = sim.replicas[0].active
    remaining_before = due - sim.now
    assert sim.page_out(0) is t
    for _ in range(10):                         # parked: the clock runs on
        sim.now += sim.dt
    # the auto fault-back path is step(); here exercise the explicit op
    assert sim.page_in(0) is t
    (_, due2), = sim.replicas[0].active
    assert due2 - sim.now == pytest.approx(remaining_before)
    run_to_completion(sim)
    sim.assert_conserved()
    tel = sim.router.fleet_telemetry()
    assert tel.paged_out == 1 and tel.paged_in == 1


@settings(max_examples=25, deadline=None)
@given(prio=st.integers(1, 4), aging_s=st.floats(0.1, 5.0),
       clock_skew=st.floats(0.0, 3.0))
def test_stolen_ticket_keeps_credit_and_is_never_a_continuation(
        prio, aging_s, clock_skew):
    """The re-stamping contract: a stolen ticket keeps tid / priority /
    deadline, its AGE (aging credit) survives even a cross-timeline move
    (rebase_pending-style accounting shifts enqueue/deadline by the
    clock delta, preserving age and slack exactly), and a continuation
    is never handed to the thief — it owns a KV slot at home."""
    pol = PriorityAgingPolicy(aging_s=aging_s)
    victim = Scheduler(pol, default_slo_ms=5_000.0)
    old = victim.submit("old", priority=prio, now=0.0)
    tid, deadline = old.tid, old.deadline_t
    cont = victim.submit("cont", priority=0, now=0.0)
    assert victim.admit(1, now=0.0) == [cont]   # rank 0 beats rank prio
    victim.resubmit(cont, now=0.01)             # now a continuation
    t_steal = prio * aging_s * 1.001            # just past the aging bound
    stolen = victim.steal_pending(5, now=t_steal)
    assert stolen == [old], "steal must skip the continuation"
    assert victim.depth == 1 and victim._pending[0] is cont
    thief = Scheduler(PriorityAgingPolicy(aging_s=aging_s))
    thief_now = t_steal + clock_skew            # thief's own timeline
    thief.absorb(stolen, now=thief_now, from_now=t_steal)
    assert old.tid == tid and old.priority == prio and old.stolen
    # age preserved exactly across the timeline shift...
    assert old.age(thief_now) == pytest.approx(t_steal)
    # ...and so is deadline slack (EDF rank survives the move)
    assert old.deadline_t - thief_now == pytest.approx(deadline - t_steal)
    thief.submit("fresh", priority=0, now=thief_now)
    # past the aging bound, the stolen ticket still outranks fresh class-0
    assert [t.payload for t in thief.admit(1, now=thief_now)] == ["old"]
    assert thief.telemetry.steals == 1


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n_replicas=st.integers(2, 4),
       n_ops=st.integers(5, 80))
def test_stealing_deterministic_under_fixed_seed(seed, n_replicas, n_ops):
    """Same seed => identical completion order, steal attribution, and
    routing — the whole steal schedule is a pure function of the seed."""
    def run():
        sim = FleetSim(replicas=n_replicas, seed=seed, steal=True,
                       service_s=[0.003 * (1 + i)
                                  for i in range(n_replicas)])
        random_schedule(sim, n_ops, skew=0.6, hot=0)
        order = run_to_completion(sim)
        return (order, list(sim.router.steals_per_replica),
                list(sim.router.routed))

    assert run() == run()


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_replicas=st.integers(2, 4),
       n=st.integers(1, 40), ticks=st.integers(0, 5),
       fail_idx=st.integers(0, 3))
def test_drain_rehomes_every_pending_ticket_exactly_once(seed, n_replicas,
                                                         n, ticks, fail_idx):
    """drain_replica moves the dead replica's whole outstanding load onto
    live queues with no loss and no duplication, counts it in the
    victim's drained counter, and is idempotent."""
    assume(fail_idx < n_replicas)               # exercises the shim too
    sim = FleetSim(replicas=n_replicas, seed=seed, steal=False)
    for _ in range(n):
        sim.submit(pin=fail_idx)
    for _ in range(ticks):
        sim.tick()
    before = Counter(sim.pending_payloads())
    victim = sim.replicas[fail_idx]
    outstanding = victim.scheduler.depth + victim.inflight
    moved = sim.fail(fail_idx)
    assert moved == outstanding
    assert victim.scheduler.depth == 0 and victim.inflight == 0
    assert Counter(sim.pending_payloads()) == before   # exactly once each
    assert victim.telemetry.drained == moved
    assert sim.fail(fail_idx) == 0              # idempotent
    note(f"moved={moved} after {ticks} ticks")
    run_to_completion(sim)
    sim.assert_conserved()


# ---- steal-aware feedback routing (PR 5) ----------------------------------

def test_feedback_steal_share_is_time_proportional():
    """ROADMAP open item closed: with route="feedback" + steal=True the
    stolen share follows the thief/victim EWMA step-time ratio. A
    3x-faster thief (EWMA 0.01 vs 0.03) takes r/(1+r) = 3/4 of the
    victim's un-startable backlog — ~3x the tickets the victim keeps —
    and fleet-wide conservation holds across the move."""
    sim = FleetSim(replicas=2, service_s=[0.03, 0.01], slots=[1, 16],
                   steal=True, route="feedback", seed=0)
    for _ in range(13):
        sim.submit(pin=0)                   # hot-keyed: all on the slow card
    backlog = sim.replicas[0].scheduler.fresh_depth - 1   # 1 startable
    assert backlog == 12
    moved = sim.router.maybe_steal(now=sim.now)
    assert moved == 9                       # round(12 * 3/4)
    kept = sim.replicas[0].scheduler.fresh_depth - 1
    assert moved == 3 * kept                # ~3x the tickets the victim keeps
    assert sim.replicas[0].scheduler.depth \
        + sim.replicas[1].scheduler.depth == 13           # conservation
    sim.assert_conserved()
    run_to_completion(sim)
    sim.assert_conserved()
    assert len(sim.completed) == 13


def test_count_mode_steal_share_stays_half():
    """Without feedback routing the share stays count-half (the PR 4
    contract is unchanged)."""
    sim = FleetSim(replicas=2, service_s=[0.03, 0.01], slots=[1, 16],
                   steal=True, route="count", seed=0)
    for _ in range(13):
        sim.submit(pin=0)
    assert sim.router.maybe_steal(now=sim.now) == 6       # 12 // 2
    run_to_completion(sim)
    sim.assert_conserved()


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), backlog=st.integers(2, 60),
       ratio=st.floats(0.2, 8.0))
def test_feedback_steal_share_bounds_and_conservation(seed, backlog, ratio):
    """Property: under feedback routing the stolen count equals
    min(cap, max(round(backlog * r / (1+r)), 1)) for speed ratio r, never
    exceeds the thief's free slots, and no ticket is lost or duplicated
    by the move."""
    victim_s = 0.01 * ratio
    sim = FleetSim(replicas=2, service_s=[victim_s, 0.01],
                   slots=[1, backlog + 4], steal=True, route="feedback",
                   seed=seed)
    for _ in range(backlog + 1):            # 1 startable + ``backlog`` stuck
        sim.submit(pin=0)
    moved = sim.router.maybe_steal(now=sim.now)
    want = max(int(round(backlog * ratio / (1.0 + ratio))), 1)
    note(f"backlog={backlog} ratio={ratio:.2f} moved={moved} want={want}")
    assert moved == min(backlog + 4, want)
    assert sim.replicas[0].scheduler.depth \
        + sim.replicas[1].scheduler.depth == backlog + 1
    sim.assert_conserved()


# ---- mixed-precision fleet (quantized serving) ----------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 50),
       n_fp32=st.integers(1, 2), n_int8=st.integers(1, 2))
def test_class0_never_lands_on_int8_while_fp32_lives(seed, n, n_fp32,
                                                     n_int8):
    """The precision-pin invariant: in a mixed fleet with live fp32
    replicas, EVERY priority-0 submit lands on an fp32 replica no matter
    how load skews (draining interleaved); bulk traffic flows freely and
    no downgrade is ever counted while fp32 capacity exists."""
    precisions = ["fp32"] * n_fp32 + ["w8a8"] * n_int8
    router = ReplicaRouter([_StubReplica(precision=p) for p in precisions])
    rng = np.random.default_rng(seed)
    for i in range(n):
        prio = int(rng.integers(0, 2))
        before = list(router.routed)
        router.submit(i, priority=prio)
        j = next(k for k in range(len(precisions))
                 if router.routed[k] != before[k])
        if prio == 0:
            assert precisions[j] == "fp32", \
                f"class-0 ticket routed to {precisions[j]} replica {j}"
        if rng.random() < 0.3:              # drain someone: loads vary
            r = router.replicas[int(rng.integers(len(precisions)))]
            if r.has_work:
                r.step_once()
    assert router.fleet_telemetry().precision_rehomed == 0


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), backlog=st.integers(2, 30))
def test_int8_thief_never_steals_class0_while_fp32_lives(seed, backlog):
    """Stealing respects the precision pin: an int8 thief pulling from a
    backlogged fp32 sibling (fp32 still live) only takes priority>0
    tickets — accuracy-pinned work stays on the fp32 card — and
    conservation holds through the move and the drain."""
    sim = FleetSim(replicas=2, service_s=[0.03, 0.01],
                   slots=[1, backlog + 2], steal=True,
                   precisions=["fp32", "w8a8"], seed=seed)
    rng = np.random.default_rng(seed)
    for _ in range(backlog + 1):            # 1 startable + backlog stuck
        sim.submit(priority=int(rng.integers(0, 3)), pin=0)
    moved = sim.router.maybe_steal(now=sim.now)
    stolen = [t for t in sim.replicas[1].scheduler._pending if t.stolen]
    assert len(stolen) == moved
    assert all(t.priority > 0 for t in stolen), \
        "int8 thief stole accuracy-pinned class-0 work"
    sim.assert_conserved()
    run_to_completion(sim)
    sim.assert_conserved()
    assert len(sim.completed) == backlog + 1


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 30),
       ticks=st.integers(0, 4))
def test_drain_of_last_fp32_rehomes_class0_to_int8_and_counts(seed, n,
                                                              ticks):
    """Graceful degradation of the pin: killing the LAST fp32 replica
    re-homes its whole outstanding load to the int8 survivor — class-0
    included, each downgrade counted in the receiver's
    precision_rehomed — and every accepted ticket still completes."""
    sim = FleetSim(replicas=2, precisions=["fp32", "w8a8"], steal=False,
                   seed=seed)
    rng = np.random.default_rng(seed)
    for _ in range(n):
        sim.submit(priority=int(rng.integers(0, 2)), pin=0)
    for _ in range(ticks):
        sim.tick()
    victim = sim.replicas[0]
    outstanding = victim.scheduler.depth + victim.inflight
    high_outstanding = \
        sum(t.priority == 0 for t in victim.scheduler._pending) \
        + sum(t.priority == 0 for t, _ in victim.active)
    moved = sim.fail(0)
    assert moved == outstanding
    assert sim.replicas[1].telemetry.precision_rehomed == high_outstanding
    sim.assert_conserved()
    run_to_completion(sim)
    sim.assert_conserved()
    assert len(sim.completed) == n          # nothing lost to the degrade


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 40))
def test_router_shed_counted_separately(seed, n):
    """Fleet-level shed accounting: shed tickets increment router.shed +
    fleet telemetry.shed, and never count as routed."""
    router = ReplicaRouter([_StubReplica(max_queue=2) for _ in range(2)])
    rng = np.random.default_rng(seed)
    shed = 0
    for i in range(n):
        t = router.submit(i)
        shed += t.shed
        if rng.random() < 0.3:
            for r in router.replicas:
                if r.has_work:
                    r.step_once()
    assert router.shed == shed
    assert router.fleet_telemetry().shed == shed
    assert sum(router.routed) == n - shed


# ---------------------------------------------------------------------------
# Elastic fleet controller (PR 7): scale events ride the SAME drain/absorb
# machinery, so the fleet-wide invariants must survive the controller
# interleaving scale-up / scale-down / fault-drain with serving.
# ---------------------------------------------------------------------------

from fleet_sim import make_controller  # noqa: E402
from repro.serving.fleet_sim import (flash_crowd_trace,  # noqa: E402
                                     multi_tenant_trace, run_elastic)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(20, 250),
       crowd_x=st.floats(1.0, 8.0), kill=st.booleans())
def test_fleet_conservation_across_scale_events(seed, n, crowd_x, kill):
    """Ticket conservation holds across ANY interleaving of submit /
    steal / scale-up / scale-down / missed-heartbeat drain: accepted =
    completed exactly, nothing duplicated (run_elastic asserts the
    multiset identity fleet-wide on exit)."""
    sim = FleetSim(replicas=2, service_s=0.01, slots=1, dt=0.005,
                   seed=seed, max_queue=16)
    ctl = make_controller(sim, min_replicas=1, max_replicas=5)
    arr = flash_crowd_trace(n, base_gap_s=0.006, crowd_x=crowd_x,
                            seed=seed, slo_ms=500.0)
    kills = [(arr[n // 2].t, 0)] if kill else []
    m = run_elastic(sim, ctl, arr, kills=kills)
    assert m["lost"] == 0
    assert m["accepted"] == m["completed"]
    assert m["submitted"] == m["completed"] + m["shed"]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(20, 150))
def test_controller_never_drains_last_live_replica(seed, n):
    """However the load and faults land, the router keeps >= 1 live
    replica: deliberate scale-down is refused at min_replicas, and a
    fault on the last live replica goes replace-then-drain."""
    sim = FleetSim(replicas=2, service_s=0.01, slots=1, dt=0.005,
                   seed=seed, max_queue=16)
    ctl = make_controller(sim, min_replicas=1, max_replicas=4)
    arr = flash_crowd_trace(n, base_gap_s=0.01, crowd_x=2.0, seed=seed)
    # both initial replicas die, well apart (detection is ~timeout_s)
    m = run_elastic(sim, ctl, arr, kills=[(arr[n // 3].t, 0),
                                          (arr[(2 * n) // 3].t, 1)])
    assert len(sim.router.alive) >= 1
    for d in ctl.decisions:
        if d.action == "down":
            assert d.live >= 1
    assert m["lost"] == 0


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_scale_down_never_burns_last_fp32(seed):
    """While mixed-precision class-0 pinning is active, deliberate
    scale-down never chooses the last live fp32 replica, no matter how
    deep the trough — the accuracy pin survives autoscaling."""
    sim = FleetSim(replicas=3, precisions=["fp32", "w8a8", "w8a8"],
                   service_s=0.01, slots=1, dt=0.005, seed=seed,
                   max_queue=16)
    ctl = make_controller(sim, min_replicas=1, max_replicas=3)
    arr = multi_tenant_trace(120, base_gap_s=0.05, seed=seed)  # light
    m = run_elastic(sim, ctl, arr)
    assert ctl.scale_downs >= 1         # the trough did shrink the fleet
    assert len(sim.router.fp32_alive) >= 1
    # replay the fleet: no deliberate down may choose the last live fp32
    # replica (a burst can scale up an fp32 joiner, after which replica 0
    # is no longer the last one)
    alive = {0, 1, 2}
    for d in ctl.decisions:
        if d.action in ("up", "replace"):
            alive.add(d.replica)
        elif d.action in ("down", "drain_failed"):
            fp32 = {i for i in alive if sim.router.precisions[i] == "fp32"}
            assert d.action != "down" or fp32 != {d.replica}, d
            alive.discard(d.replica)
    assert m["lost"] == 0


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_controller_decision_log_deterministic(seed):
    """Fixed seed -> bit-identical decision log and fleet outcome: the
    controller is a pure function of (router state, telemetry, clock)."""
    def one():
        sim = FleetSim(replicas=2, service_s=0.01, slots=1, dt=0.005,
                       seed=seed, max_queue=16)
        ctl = make_controller(sim, min_replicas=1, max_replicas=5)
        arr = flash_crowd_trace(150, base_gap_s=0.006, crowd_x=5.0,
                                seed=seed, slo_ms=500.0)
        m = run_elastic(sim, ctl, arr, kills=[(arr[75].t, 0)])
        return ([(d.now, d.action, d.replica, d.live, d.reason)
                 for d in ctl.decisions],
                m["completed"], m["shed"], m["replica_ticks"])
    assert one() == one()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), crowd_x=st.floats(1.0, 10.0))
def test_scale_decisions_respect_cooldown(seed, crowd_x):
    """Hysteresis no-flap: any two scale decisions (and any scale
    decision after a fault drain) are >= cooldown_s apart — the fleet
    can never thrash faster than the cooldown window."""
    cool = 0.3
    sim = FleetSim(replicas=2, service_s=0.01, slots=1, dt=0.005,
                   seed=seed, max_queue=16)
    ctl = make_controller(sim, min_replicas=1, max_replicas=6,
                          cooldown_s=cool)
    arr = flash_crowd_trace(200, base_gap_s=0.006, crowd_x=crowd_x,
                            seed=seed)
    run_elastic(sim, ctl, arr, kills=[(arr[100].t, 0)])
    events = [d for d in ctl.decisions
              if d.action in ("up", "down", "replace", "drain_failed")]
    for prev, cur in zip(events, events[1:]):
        if cur.action in ("up", "down"):
            assert cur.now - prev.now >= cool - 1e-9, (
                f"{cur.action} at {cur.now} only "
                f"{cur.now - prev.now:.3f}s after {prev.action}")
