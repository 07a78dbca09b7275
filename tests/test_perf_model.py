"""PR 9: the analytic serving perf model and the statistics bugs it
rode in with — interpolated-percentile boundary semantics, deterministic
calibration, the three self-tuning knobs (auto prefill chunk, suggested
bucket ladder, cold-start service priors), the ServiceEstimator
cold-start precedence, the router's per-precision EWMA scale-up seed,
and the backend-spec parameterization of the roofline terms."""
import statistics

import pytest

from repro.core.backend import (BACKENDS, TPU_V5E, BackendSpec,
                                D2H_H2D_RATIO, spec_for)
from repro.core.transfer import TransferStats
from repro.serving.perf_model import (DEFAULT_FIX_TOKENS, DEFAULT_OVERHEAD,
                                      KNEE_FRAC, _SCALE_REF_TOKENS,
                                      PerfModel)
from repro.serving.scheduler import Scheduler, ServiceEstimator
from repro.serving.telemetry import percentile

from conftest import StubReplica  # noqa: E402


# ---- interpolated percentile: the p50 lower-middle-bias fix ---------------

def test_percentile_even_n_p50_is_the_midpoint():
    """The old nearest-rank form returned the LOWER middle element at
    p=0.5 for even n; the interpolated form returns the midpoint, in
    agreement with statistics.median (the StepDeadline PR 7 precedent)."""
    assert percentile([1.0, 2.0], 0.5) == pytest.approx(1.5)
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.5) == pytest.approx(2.5)
    for vals in ([3.0, 7.0], [1.0, 5.0, 9.0], [2.0, 4.0, 8.0, 16.0]):
        assert percentile(vals, 0.5) == pytest.approx(
            statistics.median(vals))


def test_percentile_boundary_semantics():
    assert percentile([], 0.5) == 0.0
    assert percentile([5.0], 0.99) == 5.0        # n=1: the sample, always
    assert percentile([5.0], 0.0) == 5.0
    vals = [float(v) for v in range(1, 11)]
    assert percentile(vals, 0.0) == 1.0          # exact min at p=0
    assert percentile(vals, 1.0) == 10.0         # exact max at p=1
    assert percentile(vals, 1.5) == 10.0         # out-of-range p clamps
    assert percentile(vals, -0.5) == 1.0


def test_percentile_interpolates_between_ranks():
    # p99 of 100 samples: rank 99*0.99 = 98.01 -> 1% of the gap to the
    # top sample (a long-prompt outlier gets 1% weight, not zero and
    # not full)
    vals = [float(v) for v in range(100)]
    vals[99] = 1000.0
    assert percentile(vals, 0.99) == pytest.approx(98 + 0.01 * (1000 - 98))


# ---- perf model: fits, determinism, knobs ---------------------------------

def _fed_model(**kw):
    """Model with a synthetic measured line: t = 2ms + 10us/token on
    'chunk_prefill' and 'prefill' cells at 16/64/448 tokens."""
    pm = PerfModel(1e9, **kw)
    for stage in ("prefill", "chunk_prefill"):
        for bucket in (16, 64, 448):
            for rep in range(3):
                pm.observe(stage, bucket=bucket,
                           seconds=2e-3 + bucket * 10e-6)
    return pm


def test_fit_recovers_the_measured_line():
    pm = _fed_model()
    t_fix, t_tok = pm.fit_dispatch_cost("prefill")
    assert t_fix == pytest.approx(2e-3, rel=1e-6)
    assert t_tok == pytest.approx(10e-6, rel=1e-6)
    assert pm.predict_dispatch_s("prefill", 100) == pytest.approx(3e-3)
    # chunked step: ceil(448/64)=7 dispatches of 64 tokens, t_fix per chunk
    assert pm.predict_step_s("prefill", bucket=448, chunk=64) == \
        pytest.approx(7 * (2e-3 + 64 * 10e-6))


def test_calibration_is_deterministic():
    """Same samples in -> identical fitted terms and identical knob
    suggestions out (the autotune smoke relies on this)."""
    a, b = _fed_model(), _fed_model()
    assert a.fit_dispatch_cost("chunk_prefill") == \
        b.fit_dispatch_cost("chunk_prefill")
    assert a.fitted_terms() == b.fitted_terms()
    assert a.suggest_prefill_chunk((16, 64, 448)) == \
        b.suggest_prefill_chunk((16, 64, 448))
    lens = [8, 12, 9, 30, 440, 11, 14, 10]
    assert a.suggest_buckets(lens) == b.suggest_buckets(lens)


def test_cold_model_knees_from_the_default_line():
    """Unmeasured, the knee comes from the analytic default line
    (t_fix = DEFAULT_FIX_TOKENS marginal tokens): e(b) = b/(b+24), so
    the 0.75-of-top threshold lands at 32 on the smoke ladder and 64 on
    a (16, 64, 448) ladder — the values the hand-set knobs used."""
    pm = PerfModel(1e9)
    assert pm.suggest_prefill_chunk((16, 32, 64)) == 32
    assert pm.suggest_prefill_chunk((16, 64, 448)) == 64
    # efficiency is monotone, so a ladder with a LOWER top bucket can
    # only knee at or below a taller ladder's knee
    assert pm.suggest_prefill_chunk((16, 32, 64)) <= \
        pm.suggest_prefill_chunk((16, 64, 448))


def test_pinned_line_wins_over_samples_and_defaults():
    pm = _fed_model()
    pm.set_dispatch_cost("chunk_prefill", 5e-3, 1e-6)
    assert pm.fit_dispatch_cost("chunk_prefill") == (5e-3, 1e-6)
    # other stages keep their fitted lines
    assert pm.fit_dispatch_cost("prefill")[0] == pytest.approx(2e-3,
                                                               rel=1e-6)


def test_knee_respects_knee_frac_threshold():
    pm = _fed_model()
    # measured line: e(b) = 10us*b / (2ms + 10us*b); top e(448)=0.691,
    # e(64)=0.242 < 0.75*top, e(448) is first to cross -> knee = 448
    assert pm.suggest_prefill_chunk((16, 64, 448)) == 448
    # with a permissive threshold the smallest bucket qualifies
    # (e(16) = 0.074 >= 0.1 * e(448))
    assert pm.suggest_prefill_chunk((16, 64, 448), knee_frac=0.1) == 16
    with pytest.raises(ValueError):
        pm.suggest_prefill_chunk(())


def test_suggest_buckets_from_traffic_distribution():
    pm = PerfModel(1e9)
    lens = [12] * 50 + [14] * 40 + [60] * 9 + [440]
    out = pm.suggest_buckets(lens, max_len=512)
    assert out == tuple(sorted(set(out)))        # deduped, ascending
    assert all(b % 8 == 0 for b in out)          # quantum-padded
    assert out[-1] == 440                        # covers the observed max
    assert out[0] <= 16                          # p50 sits in a small bucket
    # max_len caps the ladder
    assert pm.suggest_buckets(lens, max_len=64)[-1] <= 64
    # empty traffic falls back to the default ladder
    from repro.core.bucketing import DEFAULT_BUCKETS
    assert pm.suggest_buckets([]) == DEFAULT_BUCKETS


def test_service_ratio_is_sublinear_in_bucket_size():
    """The cold-start prior: t_fix amortizes, so the predicted 448/16
    ratio sits strictly between 1 and the linear 28x guess."""
    pm = _fed_model()
    r = pm.service_ratio(448, 16)
    assert 1.0 < r < 448 / 16
    assert pm.service_ratio(16, 16) == pytest.approx(1.0)


def test_precision_scale_and_cross_precision_fallback():
    pm = _fed_model()
    assert pm.precision_scale("fp32") == pytest.approx(1.0)
    assert pm.precision_scale("w8a8") == pytest.approx(0.5)
    # no w8a8 samples: the fp32 fit rescaled by the spec ratio
    f32 = pm.fit_dispatch_cost("prefill", precision="fp32")
    w8 = pm.fit_dispatch_cost("prefill", precision="w8a8")
    assert w8[0] == pytest.approx(f32[0] * 0.5)
    assert w8[1] == pytest.approx(f32[1] * 0.5)


def test_fit_precision_scale_is_the_whole_cost_ratio():
    """Both-precision stages yield the measured multiplier; stages or
    precisions without both sides yield None (spec fallback)."""
    pm = _fed_model()
    for bucket in (16, 64, 448):
        pm.observe("chunk_prefill", bucket=bucket, precision="w8a8",
                   seconds=0.5 * (2e-3 + bucket * 10e-6))
    assert pm.fit_precision_scale("w8a8") == pytest.approx(0.5, rel=1e-6)
    assert pm.fit_precision_scale("fp32") == 1.0
    # nothing measured at int4 -> no both-sides stage -> None
    assert pm.fit_precision_scale("int4") is None
    # fp32-only model: w8a8 has no own samples either
    assert _fed_model().fit_precision_scale("w8a8") is None


def test_fit_precision_scale_survives_a_degenerate_base_fit():
    """The regression this guards: two near-equal calibration
    buckets can degenerate least-squares so the base slope clamps to
    epsilon with all cost pushed into t_fix.  The raw slope ratio then
    explodes by ~9 orders of magnitude; the whole-dispatch-cost ratio
    at _SCALE_REF_TOKENS barely notices."""
    pm = PerfModel(1e9)
    pm.set_dispatch_cost("chunk_prefill", 30e-3, 1e-12)          # degenerate
    pm.set_dispatch_cost("chunk_prefill", 0.0, 1366e-6,
                         precision="w8a8")
    n = _SCALE_REF_TOKENS
    want = (n * 1366e-6) / (30e-3 + n * 1e-12)
    got = pm.fit_precision_scale("w8a8")
    assert got == pytest.approx(want, rel=1e-9)
    assert got < 100.0                        # slope ratio would be ~1.4e9


def test_transfer_terms_carry_the_h2d_d2h_asymmetry():
    pm = PerfModel(1e9)
    stats = TransferStats()
    stats.bytes_partial = 4096.0
    stats.num_transfers_batched = 4
    terms = pm.snapshot_transfer_terms(stats)
    assert terms["bytes_per_transfer"] == pytest.approx(1024.0)
    # the D2H readback leg is ~3x slower than H2D ingest (0.868 vs
    # 0.298 words/cycle): snapshot costs more than restore
    assert terms["d2h_s"] > terms["h2d_s"]
    assert terms["d2h_h2d_ratio"] == pytest.approx(1 / D2H_H2D_RATIO)
    assert pm.transfer_s(h2d_bytes=1024) < pm.transfer_s(d2h_bytes=1024)


def test_default_overhead_constants_match_the_paper():
    # 45783 measured cycles over the 11760-cycle FMAC floor
    assert DEFAULT_OVERHEAD == pytest.approx(45783 / 11760, rel=1e-3)
    assert DEFAULT_FIX_TOKENS == 24.0
    assert KNEE_FRAC == 0.75


# ---- backend spec: the roofline constants, parameterized ------------------

def test_backend_spec_replaces_roofline_literals():
    from repro.launch.hlo_analysis import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                                           roofline_terms)
    assert PEAK_FLOPS_BF16 == TPU_V5E.peak_flops_bf16
    assert HBM_BW == TPU_V5E.hbm_bw
    assert ICI_BW == TPU_V5E.ici_bw
    # keyed by the device_kind the chip reports
    assert BACKENDS["TPU v5 lite"] is TPU_V5E

    class S:
        dot_flops = 197e12
        hbm_bytes = 819e9
        total_collective_bytes = 0.0

    t = roofline_terms(S())
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    # a different spec reprices the same summary
    half = BackendSpec(name="half", peak_flops_bf16=TPU_V5E.peak_flops_bf16
                       / 2, peak_flops_int8=TPU_V5E.peak_flops_int8 / 2,
                       hbm_bw=TPU_V5E.hbm_bw, ici_bw=TPU_V5E.ici_bw,
                       h2d_bw=TPU_V5E.h2d_bw, d2h_bw=TPU_V5E.d2h_bw)
    assert roofline_terms(S(), spec=half)["compute_s"] == pytest.approx(2.0)
    assert half.peak_flops("w8a8") == TPU_V5E.peak_flops_int8 / 2


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", TPU_V5E),
    ("cpu", "cpu", TPU_V5E),            # CPU tests price the v5e
    ("tpu", "TPU v4", None),            # unknown accelerator: an error
    ("gpu", "NVIDIA H100", None),
])
def test_spec_for_keys_on_device_kind(platform, kind, want):
    if want is None:
        with pytest.raises(ValueError, match=kind):
            spec_for(_Dev(platform, kind))
    else:
        assert spec_for(_Dev(platform, kind)) is want


def test_perf_model_prices_the_engine_device():
    import jax
    assert PerfModel.for_params({"w": 1.0}).spec is spec_for(
        jax.devices()[0])
    with pytest.raises(ValueError):
        PerfModel.for_params({"w": 1.0}, device=_Dev("tpu", "TPU v4"))


# ---- ServiceEstimator: cold-start precedence (the PR 9 bugfixes) ----------

def test_estimator_warm_bucket_uses_its_own_p50():
    est = ServiceEstimator(fallback_ms=20.0)
    for ms in (40.0, 50.0, 60.0, 50.0, 50.0):
        est.observe(10, ms)
    assert est.estimate(10) == pytest.approx(50.0)


def test_estimator_small_bucket_not_priced_off_large_samples():
    """The pooled-fallback bug: 5 completions at bucket 512 must not
    price a 10-token request at the raw 512-bucket p50 — the pooled
    estimate is rescaled from the anchor (median sampled) bucket down
    to the target's size."""
    est = ServiceEstimator(fallback_ms=20.0)
    for _ in range(5):
        est.observe(500, 800.0)                  # bucket 512, 800 ms each
    small = est.estimate(10)                     # bucket 32
    assert small == pytest.approx(800.0 * 32 / 512)   # linear, no model
    assert small < 800.0                         # never the raw pooled p50


def test_estimator_large_bucket_not_priced_off_small_samples():
    """The inverse direction, and the old test's pinned behaviour
    corrected: samples at bucket 32 price a 400-token request UP by the
    size ratio instead of handing it the raw 32-bucket p50."""
    est = ServiceEstimator(fallback_ms=20.0)
    for _ in range(5):
        est.observe(10, 50.0)                    # bucket 32
    assert est.estimate(400) == pytest.approx(50.0 * 512 / 32)


def test_estimator_static_prior_is_size_aware_for_cold_buckets():
    """Before ANY samples exist, every bucket prices off the static
    prior rescaled to its own size — and a warm bucket elsewhere must
    not hand cold buckets a worse estimate than that prior's shape
    (the 'warm bucket flips the prior off' bug: with 5 samples at one
    bucket, a cold bucket's estimate must still scale with ITS size)."""
    est = ServiceEstimator(fallback_ms=20.0)
    assert est.estimate(10) == pytest.approx(20.0)            # base bucket
    assert est.estimate(400) == pytest.approx(20.0 * 512 / 32)
    # warm up one bucket; a different cold bucket still gets a
    # size-scaled estimate, not the warm bucket's raw p50
    for _ in range(5):
        est.observe(100, 200.0)                  # bucket 128
    cold = est.estimate(400)                     # bucket 512, still cold
    assert cold == pytest.approx(200.0 * 512 / 128)
    assert cold != pytest.approx(200.0)


def test_estimator_none_without_fallback_or_samples():
    assert ServiceEstimator().estimate(10) is None


def test_estimator_prior_uses_perf_model_curve_when_wired():
    pm = _fed_model()
    est = ServiceEstimator(fallback_ms=20.0, perf_model=pm)
    linear = ServiceEstimator(fallback_ms=20.0)
    # the model's t_fix amortization prices big cold buckets below the
    # linear prior
    assert est.estimate(400) < linear.estimate(400)
    assert est.estimate(400) > 20.0


def test_scheduler_auto_estimator_threads_perf_model():
    pm = _fed_model()
    s = Scheduler("fifo", service_ms_est="auto", service_ms_fallback=20.0,
                  perf_model=pm)
    assert s._svc_auto.perf_model is pm


# ---- router: per-precision EWMA scale-up seed -----------------------------

def _fed_router(perf_model):
    from repro.serving.router import ReplicaRouter
    router = ReplicaRouter([StubReplica(), StubReplica()],
                           route="feedback", perf_model=perf_model)
    router.record_dispatch(0, 0.010)             # both fp32 cards measured
    router.record_dispatch(1, 0.010)             # at 10 ms steps
    return router


def test_scaled_up_w8a8_joiner_seeds_at_precision_scaled_cost():
    """The scale-up cold-start fix: an int8 joiner in an fp32-measured
    fleet seeds at ~half the fleet's step time (the model's precision
    ratio), not the raw fp32 mean — so feedback routing prefers it
    immediately instead of treating it as an fp32-cost card."""
    pm = _fed_model()
    router = _fed_router(pm)
    j = router.add_replica(StubReplica(precision="w8a8"))
    assert router.precisions[j] == "w8a8"
    assert router._seed_ewma(j) == pytest.approx(0.010 * 0.5)
    # fp32 joiner seeds at the unscaled fleet mean
    k = router.add_replica(StubReplica())
    assert router._seed_ewma(k) == pytest.approx(0.010)
    # and the seed drives the routing cost before any measurement:
    # empty queues everywhere, so the int8 joiner is the cheapest card
    costs = [router._cost(i) for i in range(len(router.replicas))]
    assert min(range(len(costs)), key=costs.__getitem__) == j


def test_seed_without_model_degrades_to_fleet_mean():
    router = _fed_router(None)
    router.perf_model = None
    j = router.add_replica(StubReplica(precision="w8a8"))
    assert router._seed_ewma(j) == pytest.approx(0.010)   # raw mean


def test_seed_without_measurements_is_zero_count_fallback():
    from repro.serving.router import ReplicaRouter
    router = ReplicaRouter([StubReplica(), StubReplica()],
                           route="feedback", perf_model=_fed_model())
    assert router._seed_ewma(0) == 0.0
    assert router._cost(0) == 0.0                # count fallback (empty)


def test_mixed_precision_scale_up_routes_to_the_seeded_joiner():
    """Regression for the scale-up event itself: grow a measured fp32
    fleet with a w8a8 replica mid-run and the next submits must lean on
    the joiner (cheapest estimated clearing time) rather than starving
    it until its first measurement."""
    pm = _fed_model()
    router = _fed_router(pm)
    # preload the fp32 cards so the joiner's advantage is decisive
    router.replicas[0].submit("a")
    router.replicas[1].submit("b")
    j = router.add_replica(StubReplica(precision="w8a8"))
    before = router.routed[j]
    # batch-class traffic (priority 1): the PR 6 accuracy pin only
    # routes priority-0 tickets onto fp32, so this is the class the
    # joiner is allowed to absorb
    for i in range(4):
        router.submit(i, priority=1)
    assert router.routed[j] > before             # joiner took traffic


# ---- engine: prefill_chunk="auto" resolution ------------------------------

def test_engine_auto_chunk_resolves_on_the_ladder():
    import jax
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import model as M
    from repro.serving.engine import InferenceEngine
    cfg = reduce_for_smoke(get_config("deepseek-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=64,
                          prefill_buckets=(16, 32, 64),
                          prefill_chunk="auto")
    assert isinstance(eng.prefill_chunk, int)
    assert eng.prefill_chunk in eng.buckets
    # cold analytic knee on (16, 32, 64) is 32 (see the knee test above)
    assert eng.prefill_chunk == 32
    # a calibrated model with a dominant fixed cost moves the knee up —
    # the knob follows the measurement, not a hand-set literal
    pm = PerfModel.for_params(params)
    pm.set_dispatch_cost("chunk_prefill", 24e-3, 42e-6)
    eng2 = InferenceEngine(cfg, params, batch_slots=2, max_len=64,
                           prefill_buckets=(16, 32, 64),
                           prefill_chunk="auto", perf_model=pm)
    assert eng2.prefill_chunk == 64
    assert eng2.perf_model is pm
