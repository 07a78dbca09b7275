"""Unified serving runtime: scheduler policies (FIFO/EDF/size x time),
SLA-miss accounting, slot-refill invariants, batched-prefill equivalence
vs per-request prefill, chunked-prefill equivalence vs monolithic
prefill (PR 3), TTFT telemetry, N-stage pipeline driver, stage executor
cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core.pipeline import (Pipeline, TwoStagePipeline,
                                 steady_state_speedup)
from repro.models import model as M
from repro.serving.engine import InferenceEngine, Request
from repro.serving.executor import StageExecutor
from repro.serving.scheduler import Scheduler
from repro.serving.telemetry import Telemetry


# ---- scheduler policies ---------------------------------------------------

def test_fifo_preserves_arrival_order():
    s = Scheduler("fifo")
    for i in range(5):
        s.submit(i, now=float(i))
    got = [t.payload for t in s.admit(3, now=10.0)]
    assert got == [0, 1, 2]
    assert s.depth == 2


def test_edf_orders_by_deadline():
    s = Scheduler("edf")
    s.submit("late", slo_ms=300.0, now=0.0)
    s.submit("urgent", slo_ms=50.0, now=0.0)
    s.submit("mid", slo_ms=150.0, now=0.0)
    s.submit("no-deadline", now=0.0)
    got = [t.payload for t in s.admit(4, now=0.0)]
    assert got == ["urgent", "mid", "late", "no-deadline"]


def test_edf_tie_breaks_by_arrival():
    s = Scheduler("edf", default_slo_ms=100.0)
    s.submit("a", now=0.0)
    s.submit("b", now=0.0)
    assert [t.payload for t in s.admit(2, now=0.0)] == ["a", "b"]


def test_sizetime_groups_same_bucket():
    from repro.serving.scheduler import SizeTimePolicy
    s = Scheduler(SizeTimePolicy(buckets=(32, 64)))
    # two fresh size-64 tickets vs three older size-32 tickets: the
    # size-32 group wins on count x age, and the admitted batch is
    # bucket-coherent
    for p in ("s1", "s2", "s3"):
        s.submit(p, size=20, now=0.0)
    for p in ("b1", "b2"):
        s.submit(p, size=60, now=5.0)
    got = [t.payload for t in s.admit(4, now=6.0)]
    assert got == ["s1", "s2", "s3"]
    assert s.depth == 2


def test_sla_miss_accounting():
    tel = Telemetry()
    s = Scheduler("fifo", telemetry=tel, default_slo_ms=100.0)
    from repro.serving.scheduler import NO_SLO
    t1 = s.submit("hit", now=0.0)
    t2 = s.submit("miss", now=0.0)
    t3 = s.submit("no-slo", slo_ms=NO_SLO, now=0.0)  # explicit best-effort
    assert t3.deadline_t is None
    s.admit(3, now=0.0)
    s.complete(t1, now=0.05)                  # inside the 100ms budget
    s.complete(t2, now=0.25)                  # past the deadline
    s.complete(t3, now=9.99)                  # no deadline: never a miss
    assert tel.served == 3
    assert tel.sla_total == 2
    assert tel.sla_misses == 1
    assert tel.sla_miss_frac == pytest.approx(0.5)
    assert tel.latencies_ms == pytest.approx([50.0, 250.0, 9990.0])


# ---- engine on the shared stack ------------------------------------------

@pytest.fixture(scope="module")
def lm_setup():
    cfg = reduce_for_smoke(get_config("deepseek-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _trace(cfg, seed=11, n=8, lens=(4, 6, 5, 7, 3, 6, 4, 5)):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, l).astype(np.int32),
                    max_new_tokens=4)
            for i, l in enumerate(lens[:n])]


def test_slot_refill_invariants(lm_setup):
    cfg, params = lm_setup
    eng = InferenceEngine(cfg, params, batch_slots=3, max_len=32,
                          prefill_buckets=(8, 16))
    for r in _trace(cfg):
        eng.submit(r)
    while eng.scheduler.depth or eng.active:
        eng._admit()
        eng._step()
        # every slot is exactly one of {free, active} at all times
        assert len(eng.free) + len(eng.active) == eng.batch_slots
        assert not (set(eng.free) & set(eng.active))
        assert all(0 <= s < eng.batch_slots
                   for s in list(eng.free) + list(eng.active))
    assert eng.telemetry.served == 8
    assert sorted(eng.free) == list(range(eng.batch_slots))


def test_batched_prefill_matches_per_request(lm_setup):
    """Acceptance: batched prefill is token-identical to the seed's
    one-request-at-a-time prefill on a fixed-seed trace, with fewer
    prefill dispatches."""
    cfg, params = lm_setup
    kw = dict(batch_slots=4, max_len=32, prefill_buckets=(8, 16))
    batched = InferenceEngine(cfg, params, **kw)
    got = _trace(cfg)
    batched.run(got)
    seedlike = InferenceEngine(cfg, params, max_prefill_batch=1, **kw)
    ref = _trace(cfg)
    seedlike.run(ref)

    for a, b in zip(got, ref):               # same rng -> same prompts
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.output == b.output, a.rid   # token-identical responses

    assert batched.telemetry.prefills == seedlike.telemetry.prefills == 8
    assert seedlike.telemetry.prefill_batches == 8    # one per request
    assert batched.telemetry.prefill_batches < 8      # grouped dispatches


def test_prefill_executables_bounded_per_bucket(lm_setup):
    """Groups are padded to the next power of two, so prefill executables
    per bucket are bounded at log2(slots)+1 regardless of the free-slot
    counts a trace produces (4 then 2 here -> two sizes, and a repeat of
    either size reuses its executable)."""
    cfg, params = lm_setup
    eng = InferenceEngine(cfg, params, batch_slots=4, max_len=32,
                          prefill_buckets=(8, 16))
    eng.run(_trace(cfg, n=6))          # admits groups of 4 then 2
    assert eng.telemetry.prefill_batches == 2
    assert eng.telemetry.compiles["prefill"] == 2     # P=4 and P=2
    eng.run(_trace(cfg, n=6))          # same group sizes: all cache hits
    assert eng.telemetry.compiles["prefill"] == 2


def test_per_request_slo_flows_through_engine(lm_setup):
    cfg, params = lm_setup
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=32,
                          prefill_buckets=(8,), policy="edf",
                          slo_ms=60_000.0)
    eng.run(_trace(cfg, n=4, lens=(4, 5, 3, 6)))
    assert eng.telemetry.sla_total == 4
    assert eng.telemetry.sla_misses == 0      # minute-scale SLO on smoke
    assert eng.telemetry.latency_percentiles()["p95"] > 0


# ---- chunked prefill (PR 3) ----------------------------------------------

def _mixed_trace(cfg, seed=5, lens=(40, 5, 9, 30, 3, 12, 26, 7)):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, l).astype(np.int32),
                    max_new_tokens=4)
            for i, l in enumerate(lens)]


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_token_identical_to_monolithic(lm_setup, chunk):
    """Acceptance: chunked prefill (long prompts split into chunk-sized
    continuation tickets interleaved with decode) produces exactly the
    tokens monolithic prefill produces, for every request in a mixed
    long/short trace."""
    cfg, params = lm_setup
    kw = dict(batch_slots=3, max_len=64, prefill_buckets=(8, 16, 32, 48))
    mono = InferenceEngine(cfg, params, **kw)
    ref = _mixed_trace(cfg)
    mono.run(ref)
    eng = InferenceEngine(cfg, params, prefill_chunk=chunk, **kw)
    got = _mixed_trace(cfg)
    eng.run(got)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.output == b.output, a.rid
    # the 40-token prompt really was chunked: continuations flowed
    assert eng.telemetry.continuations > 0
    assert eng.telemetry.prefills == len(got)
    assert eng.telemetry.served == len(got)


def test_chunked_executable_ladder_stops_at_chunk(lm_setup):
    """The compile-count win: the chunked engine's prefill-side programs
    are keyed by chunk bucket (<= prefill_chunk), while the monolithic
    engine compiles one program per full prompt-length bucket — long
    traffic therefore stops growing the executable ladder."""
    cfg, params = lm_setup
    kw = dict(batch_slots=1, max_len=64, prefill_buckets=(8, 16, 32, 48))
    lens = (40, 20, 12, 6)             # spans buckets 8..48 monolithically
    mono = InferenceEngine(cfg, params, **kw)
    mono.run(_mixed_trace(cfg, lens=lens))
    eng = InferenceEngine(cfg, params, prefill_chunk=16, **kw)
    eng.run(_mixed_trace(cfg, lens=lens))
    mono_buckets = {k[1][0] for k in mono.executor.cached_keys("prefill")}
    chunk_buckets = {k[1][0] for k in
                     eng.executor.cached_keys("chunk_prefill")}
    assert max(mono_buckets) > 16       # monolithic compiled a long bucket
    assert max(chunk_buckets) <= 16     # chunked ladder capped at chunk
    assert not eng.executor.cached_keys("prefill")
    assert eng.telemetry.compiles["chunk_prefill"] \
        < mono.telemetry.compiles["prefill"]


def test_chunked_slot_states_partition(lm_setup):
    """Every slot is exactly one of {free, active, prefilling} at every
    tick, and mid-prefill requests hold their slot across continuation
    re-admissions."""
    cfg, params = lm_setup
    eng = InferenceEngine(cfg, params, batch_slots=3, max_len=64,
                          prefill_buckets=(8, 16, 32, 48),
                          prefill_chunk=8)
    for r in _mixed_trace(cfg):
        eng.submit(r)
    saw_prefilling = False
    while eng.has_work:
        eng.step_once()
        states = (len(eng.free) + len(eng.active) + len(eng.prefilling))
        assert states == eng.batch_slots
        assert not (set(eng.free) & set(eng.active))
        assert not (set(eng.free) & set(eng.prefilling.values()))
        assert not (set(eng.active) & set(eng.prefilling.values()))
        saw_prefilling |= bool(eng.prefilling)
    assert saw_prefilling               # the long prompt went multi-chunk
    assert sorted(eng.free) == list(range(eng.batch_slots))


def test_chunked_capability_check_is_precise(lm_setup):
    """PR 5 lifted the all-global gate: mixed global/local (and SSM /
    RG-LRU) stacks chunk; only kinds with no per-slot chunk contract
    (cross-attention encoder-decoder) still raise, naming the kind."""
    cfg, params = lm_setup
    import dataclasses
    from repro.configs.base import ATTN_GLOBAL, ATTN_LOCAL
    mixed = dataclasses.replace(cfg, num_layers=2,
                                block_pattern=(ATTN_GLOBAL, ATTN_LOCAL),
                                window_size=16)
    eng = InferenceEngine(mixed, params, prefill_chunk=8, batch_slots=2,
                          max_len=32, prefill_buckets=(8,))
    assert eng.prefill_chunk == 8
    encdec = reduce_for_smoke(get_config("whisper-medium"))
    with pytest.raises(ValueError, match="decoder"):
        InferenceEngine(encdec, M.init_params(encdec, jax.random.PRNGKey(0)),
                        prefill_chunk=8, batch_slots=2, max_len=32,
                        prefill_buckets=(8,))


# ---- stateful chunked prefill (PR 5): every block pattern chunks ----------

def _arch_cfg(name):
    """Smoke configs covering every slot-state kind: pure local ring,
    pure SSM, pure RG-LRU, and the two hybrid patterns."""
    import dataclasses
    from repro.configs.base import ATTN_LOCAL, RECURRENT
    if name == "local":
        return dataclasses.replace(reduce_for_smoke(get_config("deepseek-7b")),
                                   block_pattern=(ATTN_LOCAL,), window_size=8)
    if name == "ssm":
        return reduce_for_smoke(get_config("mamba2-130m"))
    if name == "rglru":
        return dataclasses.replace(
            reduce_for_smoke(get_config("recurrentgemma-9b")),
            block_pattern=(RECURRENT,))
    if name == "hybrid-local-global":
        return reduce_for_smoke(get_config("gemma2-27b"))
    if name == "hybrid-rec-rec-local":
        return reduce_for_smoke(get_config("recurrentgemma-9b"))
    raise ValueError(name)


STATEFUL_ARCHS = ("local", "ssm", "rglru", "hybrid-local-global",
                  "hybrid-rec-rec-local")


@pytest.mark.parametrize("arch", STATEFUL_ARCHS)
def test_stateful_chunked_prefill_token_identical(arch):
    """Acceptance (PR 5): chunked prefill is token-identical to
    monolithic prefill for every block pattern — local rings write at
    chunk offsets, SSM / RG-LRU carry the entering state + conv tail
    across chunk boundaries — across trace seeds and chunk sizes."""
    cfg = _arch_cfg(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(batch_slots=3, max_len=64, prefill_buckets=(8, 16, 32, 48))
    lens = (40, 5, 9, 30, 3, 12)
    # one engine per (mode, chunk), reused across trace seeds — the
    # executor cache keeps the compiled stages warm between seeds
    mono = InferenceEngine(cfg, params, **kw)
    chunked = {c: InferenceEngine(cfg, params, prefill_chunk=c, **kw)
               for c in (8, 16)}
    for seed in (5, 11):
        ref = _mixed_trace(cfg, seed=seed, lens=lens)
        mono.run(ref)
        for chunk, eng in chunked.items():
            before = eng.telemetry.continuations
            got = _mixed_trace(cfg, seed=seed, lens=lens)
            eng.run(got)
            for a, b in zip(got, ref):
                np.testing.assert_array_equal(a.tokens, b.tokens)
                assert a.output == b.output, (arch, seed, chunk, a.rid)
            assert eng.telemetry.continuations > before   # really chunked
            assert all(r.done for r in got)


def test_chunked_slot_partition_holds_for_stateful_arch():
    """The SequenceStateManager partition invariant under a live chunked
    run on a recurrent stack: free | active | prefilling at every tick."""
    cfg = _arch_cfg("hybrid-rec-rec-local")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, batch_slots=3, max_len=64,
                          prefill_buckets=(8, 16, 32, 48), prefill_chunk=8)
    for r in _mixed_trace(cfg):
        eng.submit(r)
    saw_prefilling = False
    while eng.has_work:
        eng.step_once()
        eng.states.check_partition()
        saw_prefilling |= bool(eng.prefilling)
    assert saw_prefilling
    assert sorted(eng.free) == list(range(eng.batch_slots))


def test_ttft_recorded_for_both_prefill_paths(lm_setup):
    """TTFT (enqueue -> first token) lands in telemetry for monolithic
    and chunked engines alike: one sample per served request, bounded
    above by full latency, surfaced in summary() and report()."""
    cfg, params = lm_setup
    kw = dict(batch_slots=2, max_len=64, prefill_buckets=(8, 16, 32, 48))
    for chunk in (None, 8):
        eng = InferenceEngine(cfg, params, prefill_chunk=chunk, **kw)
        eng.run(_mixed_trace(cfg))
        tel = eng.telemetry
        assert len(tel.ttft_ms) == tel.served == 8
        pct = tel.ttft_percentiles()
        assert 0 < pct["p50"] <= pct["p99"]
        lat = tel.latency_percentiles()
        assert pct["max"] <= lat["max"]
        assert "ttft_ms_p99" in tel.summary()
        assert "TTFT ms" in tel.report()


def test_chunked_run_deterministic(lm_setup):
    cfg, params = lm_setup
    kw = dict(batch_slots=2, max_len=64, prefill_buckets=(8, 16, 32),
              prefill_chunk=8)
    a = InferenceEngine(cfg, params, **kw)
    ra = _mixed_trace(cfg)
    a.run(ra)
    b = InferenceEngine(cfg, params, **kw)
    rb = _mixed_trace(cfg)
    b.run(rb)
    assert [r.output for r in ra] == [r.output for r in rb]


# ---- quantized serving (PR 6): int8 KV under chunking + w8a8 accuracy -----

# Chunked prefill under an int8 KV cache attends the DEQUANTIZED cached
# prefix for every chunk after the first, while monolithic prefill attends
# the exact in-pass K/V — so token identity is not guaranteed by
# construction and the contract is an explicit agreement bound instead
# (core.metrics.token_agreement: attributable — per request, tokens count
# only until the first mismatch). Measured 1.00 on the smoke stack across
# archs/seeds/chunks; the bound leaves headroom for numerics drift
# without masking a real regression.
INT8_KV_CHUNK_AGREE = 0.95
# w8a8 projections vs fp32: same greedy-token-agreement contract as
# launch/serve.py's --verify-quant (QUANT_AGREEMENT_THRESHOLD).
W8A8_AGREE = 0.90


def _token_agreement(got, ref):
    from repro.core.metrics import token_agreement
    return token_agreement([(a.output, b.output)
                            for a, b in zip(got, ref)])


def _int8_kv_cfg(arch):
    """Attention-bearing smoke configs (SSM/RG-LRU have no KV cache) with
    the paper-T3 int8 KV cache switched on — covers the k_scale branches
    of mono prefill, chunked global scatter, and the local ring."""
    import dataclasses
    if arch == "global":
        cfg = reduce_for_smoke(get_config("deepseek-7b"))
    else:
        cfg = _arch_cfg(arch)
    return dataclasses.replace(
        cfg, quant=dataclasses.replace(cfg.quant, kv_cache_dtype="int8"))


@pytest.mark.parametrize("arch", ("global", "local", "hybrid-local-global"))
def test_int8_kv_chunked_prefill_agreement_bound(arch):
    """Acceptance (PR 6): chunked prefill over an int8 KV cache stays
    within the greedy-token agreement bound of monolithic int8-KV prefill
    on every attention-bearing block pattern, with continuations really
    flowing (the dequantized-prefix chunk branches execute)."""
    cfg = _int8_kv_cfg(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(batch_slots=3, max_len=64, prefill_buckets=(8, 16, 32, 48))
    mono = InferenceEngine(cfg, params, **kw)
    for seed in (5, 11):
        ref = _mixed_trace(cfg, seed=seed)
        mono.run(ref)
        for chunk in (8, 16):
            eng = InferenceEngine(cfg, params, prefill_chunk=chunk, **kw)
            got = _mixed_trace(cfg, seed=seed)
            eng.run(got)
            assert eng.telemetry.continuations > 0
            agreement = _token_agreement(got, ref)
            assert agreement >= INT8_KV_CHUNK_AGREE, \
                (arch, seed, chunk, agreement)


def test_int8_kv_chunked_survives_work_stealing():
    """int8 KV + chunked prefill + cross-replica stealing compose: a
    fully-skewed trace on a 2-replica fleet really steals (fresh tickets
    move, continuations are pinned), nothing is lost, and fleet outputs
    stay within the agreement bound of a single mono int8-KV engine."""
    from repro.serving.router import ReplicaRouter
    cfg = _int8_kv_cfg("global")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(batch_slots=3, max_len=64, prefill_buckets=(8, 16, 32, 48))
    mono = InferenceEngine(cfg, params, **kw)
    ref = _mixed_trace(cfg)
    mono.run(ref)
    reps = [InferenceEngine(cfg, params, prefill_chunk=8, **kw)
            for _ in range(2)]
    router = ReplicaRouter(reps, steal=True)
    got = _mixed_trace(cfg)
    for r in got:
        reps[0].submit(r)                 # hot-keyed skew: all on one card
    router.run_until_drained()
    tel = router.fleet_telemetry()
    assert tel.served == len(got)
    assert tel.steals > 0                 # the sibling really pulled work
    assert tel.continuations > 0
    assert all(r.done for r in got)
    assert _token_agreement(got, ref) >= INT8_KV_CHUNK_AGREE


def test_w8a8_engine_agreement_bound(lm_setup):
    """Acceptance (PR 6): the w8a8 engine (per-channel int8 weights,
    dynamic per-row activation scales) matches fp32 greedy decoding
    within the agreement threshold, monolithic and chunked alike,
    and its executables are cached under the precision-qualified key."""
    cfg, params = lm_setup
    kw = dict(batch_slots=3, max_len=64, prefill_buckets=(8, 16, 32, 48))
    ref = _mixed_trace(cfg)
    InferenceEngine(cfg, params, **kw).run(ref)
    for chunk in (None, 8):
        eng = InferenceEngine(cfg, params, precision="w8a8",
                              prefill_chunk=chunk, **kw)
        got = _mixed_trace(cfg)
        eng.run(got)
        assert all(r.done for r in got)
        agreement = _token_agreement(got, ref)
        assert agreement >= W8A8_AGREE, (chunk, agreement)
        stage = "chunk_prefill" if chunk else "prefill"
        assert all(k[1][-1] == "w8a8"
                   for k in eng.executor.cached_keys(stage))


# ---- N-stage pipeline -----------------------------------------------------

def test_nstage_pipeline_matches_sequential():
    stages = [
        ("load", lambda x, req: jnp.asarray(req, jnp.float32)),
        ("double", jax.jit(lambda x, req: x * 2.0)),
        ("inc", jax.jit(lambda x, req: x + 1.0)),
        ("square", jax.jit(lambda x, req: x * x)),
    ]
    pipe = Pipeline(stages)
    assert pipe.num_stages == 4
    reqs = [float(i) for i in range(9)]
    outs, _ = pipe.run(reqs)
    outs_seq, _ = pipe.run_sequential(reqs)
    expect = [(2.0 * r + 1.0) ** 2 for r in reqs]
    for a, b, e in zip(outs, outs_seq, expect):
        assert float(a) == float(b) == e


def test_nstage_measure_times_every_stage():
    pipe = Pipeline([("a", lambda x, r: jnp.float32(r)),
                     ("b", jax.jit(lambda x, r: x + 1))])
    _, stats = pipe.run([1.0, 2.0], measure=True)
    assert set(stats.stage_time_s) == {"a", "b"}
    assert all(v >= 0 for v in stats.stage_time_s.values())


def test_two_stage_alias_back_compat():
    pipe = TwoStagePipeline(lambda r: jnp.asarray(r) * 2.0,
                            lambda s, r: s + 1.0)
    assert pipe.stage_names == ["sparse", "dense"]
    outs, stats = pipe.run([jnp.float32(i) for i in range(5)],
                           measure=True)
    assert [float(o) for o in outs] == [1.0, 3.0, 5.0, 7.0, 9.0]
    assert stats.sparse_time_s >= 0 and stats.dense_time_s >= 0


def test_steady_state_speedup_nstage():
    assert steady_state_speedup(1.0, 1.0) == pytest.approx(2.0)
    assert steady_state_speedup(1.0, 1.0, 2.0) == pytest.approx(2.0)
    assert steady_state_speedup(1.0, 3.0) == pytest.approx(4.0 / 3.0)


# ---- stage executor -------------------------------------------------------

def test_executor_caches_per_stage_and_key():
    tel = Telemetry()
    ex = StageExecutor(tel)
    builds = []

    def builder(tag):
        def build():
            builds.append(tag)
            return lambda x: x + tag
        return build

    assert ex.dispatch("add", 1, builder(1), 10) == 11
    assert ex.dispatch("add", 1, builder(1), 20) == 21   # cache hit
    assert ex.dispatch("add", 2, builder(2), 10) == 12   # new key
    assert builds == [1, 2]
    assert tel.compiles == {"add": 2}
    assert tel.stage_calls == {"add": 3}
    assert ex.cached_keys("add") == [("add", 1), ("add", 2)]


# ---- movable sequence state (PR 8): one snapshot contract, three movers ---

def _shared_prefix_trace(cfg, *, seed=3, prefix_len=24,
                         lens=(10, 6, 12), max_new=4):
    """Requests sharing one system prompt (the prefix-cache workload)."""
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    rng = np.random.default_rng(seed)
    return [Request(i, np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, l)]).astype(np.int32),
                    max_new_tokens=max_new)
            for i, l in enumerate(lens)]


def _leaves_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


SNAPSHOT_ARCHS = ("global", "local", "ssm", "hybrid-rec-rec-local",
                  "int8-global", "int8-hybrid")


@pytest.mark.parametrize("arch", SNAPSHOT_ARCHS)
def test_snapshot_restore_round_trip_per_state_kind(arch):
    """Acceptance (PR 8): serialize -> restore is an identity for every
    slot-state kind — positional K/V rows (and their int8 scales) sliced
    to the written prefix, ring / recurrent / conv-tail state moved
    whole — landing in a DIFFERENT free slot, after a chunked run that
    exercised padded-bucket rows (group of 3 -> P=4)."""
    import dataclasses as _dc
    if arch.startswith("int8-"):
        base = "global" if arch == "int8-global" else "hybrid-local-global"
        cfg = _int8_kv_cfg(base)
    else:
        cfg = (reduce_for_smoke(get_config("deepseek-7b"))
               if arch == "global" else _arch_cfg(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, prefill_chunk=8, batch_slots=3,
                          max_len=64, prefill_buckets=(8, 16, 32, 48))
    eng.run(_mixed_trace(cfg, seed=7, lens=(16, 9, 5)))
    if arch.startswith("int8-"):
        dts = {np.asarray(l).dtype for l in jax.tree.leaves(eng.caches)}
        assert np.dtype(np.int8) in dts       # the scales branch is live
    src = eng.snapshot_slot(0, 16)
    assert src.length == 16
    # the staged-path accounting: one batched device_get, and on a
    # positional-cache arch the prefix slice really saves bytes
    assert eng.transfer_stats.num_transfers_batched >= 1
    if arch in ("global", "int8-global"):
        assert src.bytes_partial < src.bytes_full
    eng.restore_slot(src, 2)
    back = eng.snapshot_slot(2, 16)
    _leaves_equal(src.leaves, back.leaves)
    # restore composes with the partition: a second hop lands identically
    eng.restore_slot(back, 1)
    _leaves_equal(src.leaves, eng.snapshot_slot(1, 16).leaves)


def test_prefix_cache_requires_chunking(lm_setup):
    cfg, params = lm_setup
    with pytest.raises(ValueError, match="prefill_chunk"):
        InferenceEngine(cfg, params, prefix_cache=8, batch_slots=2,
                        max_len=64)


def test_prefix_cache_hits_token_identical(lm_setup):
    """Acceptance (PR 8): requests admitted with a cached prefix emit
    token-identical output to a cold engine — the final chunk always
    recomputes, so the first token goes through the same math — while
    ``prefix_hits`` counts every warm admission and hit tickets are
    steal-vetoed until their restore lands."""
    cfg, params = lm_setup
    kw = dict(batch_slots=3, max_len=64, prefill_buckets=(8, 16, 32, 48),
              prefill_chunk=8)
    cold_eng = InferenceEngine(cfg, params, **kw)
    cold = _shared_prefix_trace(cfg)
    cold_eng.run(cold)
    eng = InferenceEngine(cfg, params, prefix_cache=32, **kw)
    eng.run(_shared_prefix_trace(cfg))          # pass 1 populates
    assert len(eng._prefix_cache) > 0
    warm = _shared_prefix_trace(cfg)
    tickets = [eng.submit(r) for r in warm]
    # every warm prompt found the shared system prefix at submit...
    assert eng.telemetry.prefix_hits >= len(warm)
    # ...and a hit ticket may NOT be stolen while its snapshot is local
    assert all(not eng.steal_eligible(t) for t in tickets)
    while eng.has_work:
        eng.step_once()
        eng.states.check_partition()
    for a, b in zip(warm, cold):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.output == b.output, (a.rid, a.output, b.output)
    assert not eng._pending_restore


def test_prefix_cache_lru_bounded(lm_setup):
    """The cache never exceeds its entry cap; eviction is LRU."""
    cfg, params = lm_setup
    eng = InferenceEngine(cfg, params, prefix_cache=2, prefill_chunk=8,
                          batch_slots=3, max_len=64,
                          prefill_buckets=(8, 16, 32, 48))
    eng.run(_mixed_trace(cfg, seed=5, lens=(40, 30, 26, 33)))
    assert len(eng._prefix_cache) <= 2


def test_paging_serves_more_sessions_than_slots(lm_setup):
    """Acceptance (PR 8): with host-RAM paging a 2-slot engine serves 6
    concurrent sessions with ZERO loss and outputs token-identical to a
    6-slot engine — slot count no longer bounds concurrency — with the
    partition exact at every tick and real page traffic."""
    cfg, params = lm_setup
    lens = (40, 5, 9, 30, 3, 12)
    big = InferenceEngine(cfg, params, prefill_chunk=8, batch_slots=6,
                          max_len=64, prefill_buckets=(8, 16, 32, 48))
    ref = _mixed_trace(cfg, seed=9, lens=lens)
    big.run(ref)
    eng = InferenceEngine(cfg, params, prefill_chunk=8, batch_slots=2,
                          max_len=64, prefill_buckets=(8, 16, 32, 48),
                          page_host=True)
    got = _mixed_trace(cfg, seed=9, lens=lens)
    for r in got:
        eng.submit(r)
    assert eng.inflight + eng.scheduler.depth == len(got)   # none shed
    while eng.has_work:
        eng.step_once()
        eng.states.check_partition()
    tel = eng.telemetry
    assert tel.served == len(got) and all(r.done for r in got)
    assert tel.paged_out > 0 and tel.paged_in > 0
    assert tel.paged_in == tel.paged_out        # every park faulted back
    assert not eng._paged
    for a, b in zip(got, ref):
        assert a.output == b.output, (a.rid, a.output, b.output)
    assert sorted(eng.free) == list(range(2))


def test_page_victim_policy_pins_both_orderings(lm_setup):
    """Regression (PR 10 satellite): the paging victim policy. The
    default ``"lru"`` parks the slot whose last decoded token is OLDEST
    (the longest-idle session, ties to the lowest slot);
    ``page_victim="remaining"`` keeps the pre-PR-10 most-service-
    remaining heuristic (ties to the highest slot). Identical engine
    state must produce DIFFERENT victims under the two policies — both
    orderings pinned, so a silent swap of the default fails loudly."""
    cfg, params = lm_setup
    kw = dict(prefill_chunk=8, batch_slots=3, max_len=64,
              prefill_buckets=(8, 16, 32, 48), page_host=True)

    def activate(eng):
        # three sessions with distinct service remaining: rid 2 (30 new
        # tokens) is the "remaining" victim regardless of idleness
        for i, mnt in enumerate((20, 24, 30)):
            rng = np.random.default_rng(40 + i)
            eng.submit(Request(i, rng.integers(0, cfg.vocab_size, 5 + i)
                               .astype(np.int32), max_new_tokens=mnt))
        while len(eng.states.active) < 3:
            eng.step_once()
        return {s: t.payload.rid for s, t in eng.states.active.items()}

    lru = InferenceEngine(cfg, params, **kw)
    assert lru.page_victim == "lru"             # the default policy
    slots = activate(lru)
    lru._last_decode = {0: 9, 1: 2, 2: 7}       # slot 1 idle longest
    assert lru._page_out_one()
    assert 1 not in lru.states.active
    (t, _snap), = lru._paged.values()
    assert t.payload.rid == slots[1]

    rem = InferenceEngine(cfg, params, page_victim="remaining", **kw)
    slots_r = activate(rem)
    rem._last_decode = {0: 9, 1: 2, 2: 7}       # ignored by this policy
    assert rem._page_out_one()
    (t_r, _snap), = rem._paged.values()
    assert t_r.payload.rid == 2                 # most tokens still to go
    victim_slot = next(s for s, rid in slots_r.items() if rid == 2)
    assert victim_slot not in rem.states.active
    assert t_r.payload.rid != t.payload.rid     # the policies disagree

    with pytest.raises(ValueError):
        InferenceEngine(cfg, params, page_victim="mru", **kw)


def test_mid_prefill_migration_resumes_from_chunk(lm_setup):
    """Acceptance (PR 8): under ``migrate=True`` an idle replica adopts a
    loaded sibling's mid-prefill continuation WITH its snapshot — the
    thief resumes from the last completed chunk (adoption sees the exact
    chunk-boundary offset, never zero), outputs stay token-identical to
    an unmigrated engine, and the moves land in ``migrated``, not
    ``steals``."""
    from repro.serving.router import ReplicaRouter
    cfg, params = lm_setup
    kw = dict(batch_slots=3, max_len=64, prefill_buckets=(8, 16, 32, 48),
              prefill_chunk=8)
    lens = (40, 38, 36, 30, 33, 12)
    mono = InferenceEngine(cfg, params, **kw)
    ref = _mixed_trace(cfg, seed=5, lens=lens)
    mono.run(ref)
    reps = [InferenceEngine(cfg, params, **kw) for _ in range(2)]
    adopted = []                    # (prefill_pos at adoption, snap.length)
    orig = reps[1].adopt_prefill
    reps[1].adopt_prefill = lambda t, snap: (
        adopted.append((t.payload.prefill_pos, snap.length)), orig(t, snap))
    router = ReplicaRouter(reps, steal=False, migrate=True)
    got = _mixed_trace(cfg, seed=5, lens=lens)
    for r in got:
        reps[0].submit(r)           # hot-keyed skew: replica 1 sits idle
    router.run_until_drained()
    tel = router.fleet_telemetry()
    assert tel.migrated > 0 and tel.steals == 0
    assert tel.migrated == len(adopted)
    for pos, length in adopted:
        assert pos == length        # the snapshot ships the whole prefix
        assert pos >= 8             # >= one completed chunk: no zero-restart
        assert pos % 8 == 0         # chunk-boundary resume offset
    for a, b in zip(got, ref):
        assert a.output == b.output, (a.rid, a.output, b.output)
    for e in reps:
        e.states.check_partition()
        assert sorted(e.free) == list(range(3))


def test_snapshot_counters_round_trip_summary(lm_setup):
    """The four PR 8 counters surface in summary() and merge correctly
    through fleet aggregation (the report path smoke)."""
    tel = Telemetry()
    tel.record_prefix_hit()
    tel.record_paged_out(2)
    tel.record_paged_in(2)
    tel.record_migrated(3)
    s = tel.summary()
    assert (s["prefix_hits"], s["paged_out"], s["paged_in"],
            s["migrated"]) == (1, 2, 2, 3)
    merged = Telemetry.merged([tel, Telemetry()])
    assert merged.migrated == 3 and merged.prefix_hits == 1
    rep = tel.report()
    assert "prefix" in rep and "paging" in rep and "migrated" in rep
