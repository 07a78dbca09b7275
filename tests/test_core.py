"""core/ package tests: partitioner (T1/T8), bucketing (T5), transfers (T6),
host split (T7), pipeline (T2), metrics, numerics harness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import DLRM_CONFIGS, dlrm_paper
from repro.core import bucketing as B
from repro.core import host_split as HS
from repro.core import metrics as MET
from repro.core import partitioner as PT
from repro.core import transfer as TR
from repro.core.numerics import GoldenSet
from repro.core.pipeline import TwoStagePipeline, steady_state_speedup


# ---- partitioner ---------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(n=st.integers(4, 48), shards=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 10**6))
def test_partition_assigns_every_table_once(n, shards, seed):
    rng = np.random.default_rng(seed)
    rows = rng.integers(100, 10000, n).tolist()
    looks = rng.integers(1, 64, n).tolist()
    asn = PT.partition_tables(rows, shards, looks)
    assert sorted(t for ts in asn.tables_of_shard for t in ts) == list(range(n))
    # every table's rows fit inside its shard's slab range
    for t in range(n):
        s = asn.shard_of_table[t]
        lo, hi = s * asn.rows_per_shard, (s + 1) * asn.rows_per_shard
        assert lo <= asn.table_offset[t]
        assert asn.table_offset[t] + rows[t] <= hi


def _skewed_tables():
    """Big tables with few lookups, small hot tables."""
    rng = np.random.default_rng(7)
    rows = [10_000_000] * 8 + [10_000] * 24
    looks = [1] * 8 + list(rng.integers(40, 80, 24))
    return rows, looks, 1


def _paper_tables(name):
    cfg = DLRM_CONFIGS[name]
    return cfg.table_rows, cfg.avg_lookups_per_table, cfg.embed_dim


@pytest.mark.parametrize("tables,shards", [
    ("skewed", 6),
    *[(name, shards) for name in sorted(DLRM_CONFIGS)
      for shards in (6, 16, 32)],
])
def test_length_aware_beats_naive_on_skew(tables, shards):
    """Paper §VI-B: 15-34% SLS latency reduction with length info, on a
    skewed workload and on the paper's two DLRM configs at the paper's
    6-card system and at mesh scales."""
    rows, looks, dim = (_skewed_tables() if tables == "skewed"
                        else _paper_tables(tables))
    rep = PT.balance_report(rows, looks, shards, dim)
    assert rep["latency_reduction"] > 0.15, rep
    assert rep["aware_imbalance"] < rep["naive_imbalance"]


def test_allocate_cores_matches_paper_ratio():
    """With sparse ~= half of dense cost, ~1/3 of cores go to SLS (paper)."""
    cs, t = PT.allocate_cores(sparse_cost=1.0, dense_cost=2.0, num_cores=12)
    assert cs == 4
    assert t == pytest.approx(0.25)


# ---- bucketing -----------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(l=st.integers(1, 600))
def test_pick_bucket_covers(l):
    b = B.pick_bucket(l, B.DEFAULT_BUCKETS)
    if l <= max(B.DEFAULT_BUCKETS):
        assert b >= l
        smaller = [x for x in B.DEFAULT_BUCKETS if x >= l]
        assert b == min(smaller)
    else:
        assert b == max(B.DEFAULT_BUCKETS)


def test_bucketed_executable_compiles_once_per_bucket():
    calls = []

    def build(bucket):
        calls.append(bucket)
        return lambda toks, mask: toks.shape
    ex = B.BucketedExecutable(build, buckets=(8, 16, 32))
    seqs = [np.arange(5), np.arange(7)]
    assert ex(seqs) == (2, 8)
    assert ex([np.arange(6)]) == (1, 8)
    assert ex([np.arange(20)]) == (1, 32)
    assert calls == [8, 32]
    assert ex.compile_count == 2


def test_length_sorted_batching_cuts_waste():
    rng = np.random.default_rng(0)
    lengths = rng.lognormal(3.2, 0.8, 512).astype(int).clip(4, 512).tolist()
    naive = B.wasted_compute_fraction(lengths, B.DEFAULT_BUCKETS)
    batches = B.length_sorted_batches(lengths, 16)
    sorted_waste = np.mean([
        B.wasted_compute_fraction([max(lengths[i] for i in b)] * len(b),
                                  B.DEFAULT_BUCKETS)
        - (1 - np.mean([lengths[i] for i in b])
           / max(lengths[i] for i in b)) * 0
        for b in batches])
    # grouping similar lengths shouldn't increase padding waste
    assert sorted_waste <= naive + 0.25


# ---- transfers -----------------------------------------------------------

def test_partial_transfer_roundtrip(rng):
    bags = [[[int(x) for x in rng.integers(0, 100, rng.integers(0, 5))]
             for _ in range(6)] for _ in range(4)]
    sb = TR.pack_sparse_inputs(bags, num_tables=6, max_lookups=8)
    stats = TR.TransferStats()
    idx, lens = TR.command_batched_transfer(sb, stats)
    np.testing.assert_array_equal(np.asarray(idx), sb.indices)
    np.testing.assert_array_equal(np.asarray(lens), sb.lengths)
    assert stats.bytes_partial < stats.bytes_full
    assert stats.num_transfers_batched < stats.num_transfers_naive


def test_live_share_counts_lookups_over_gathered_slots():
    assert TR.TransferStats().live_share == 0.0
    bags = [[[5, 6, 7], [8]], [[], [9, 10]]]       # items of 4 and 2 lookups
    stats = TR.TransferStats()
    staged, _ = TR.staged_transfer(
        TR.pack_sparse_inputs(bags, num_tables=2, max_lookups=3), stats)
    np.testing.assert_array_equal(np.asarray(staged),
                                  [[5, 6, 7, 8], [9, 10, 0, 0]])
    assert (stats.rows_live, stats.rows_gathered) == (6, 8)
    assert stats.live_share == 0.75


@pytest.mark.parametrize("bad", [4, -1])
def test_staged_transfer_refuses_lengths_outside_the_bag_limit(bad):
    sb = TR.pack_sparse_inputs([[[1, 2], [3]]], num_tables=2, max_lookups=3)
    sb.lengths[0, 1] = bad
    with pytest.raises(ValueError):
        TR.staged_transfer(sb)


def _one_lookup_bags(rng):
    bags = [[[1] for _ in range(16)] for _ in range(8)]   # 1 of 64 slots used
    return TR.pack_sparse_inputs(bags, num_tables=16, max_lookups=64)


def _paper_complex_bags(rng, batch=64):
    """PAPER_COMPLEX's index shape (96 tables x 128 max lookups) with
    Poisson bags around its per-table average lookups."""
    cfg = dlrm_paper.PAPER_COMPLEX
    T, L = cfg.num_tables, cfg.max_lookups_per_table
    avg = np.asarray(cfg.avg_lookups_per_table)
    lengths = np.minimum(rng.poisson(avg[None, :], (batch, T)) + 1,
                         L).astype(np.int32)
    indices = np.zeros((batch, T, L), np.int32)
    for t in range(T):
        k = int(lengths[:, t].max())
        indices[:, t, :k] = rng.integers(0, 10_000, (batch, k))
    return TR.SparseBatch(indices, lengths)


@pytest.mark.parametrize("make_batch,min_saved", [
    (_one_lookup_bags, 0.9), (_paper_complex_bags, 0.7)],
    ids=["one-lookup", "paper-complex"])
def test_partial_transfer_saves_most_bytes_when_sparse(rng, make_batch,
                                                       min_saved):
    sb = make_batch(rng)
    stats = TR.TransferStats()
    TR.command_batched_transfer(sb, stats)
    assert stats.bytes_saved_frac > min_saved
    # command batching: staged ids + lengths against one put per table
    assert stats.num_transfers_batched == 2
    assert stats.num_transfers_naive == sb.indices.shape[1] + 1


# ---- host split ----------------------------------------------------------

def test_split_keeps_unsupported_on_host():
    ops = [HS.OpSpec("tokenize", 1e3, 100, 400, supported_on_device=False),
           HS.OpSpec("embed", 1e9, 400, 4000),
           HS.OpSpec("transformer", 1e12, 4000, 4000)]
    dec = HS.split_net(ops)
    assert "tokenize" in dec.host_ops
    assert "transformer" in dec.device_ops


def test_broadcast_policy_prefers_concat_single_broadcast():
    res = HS.broadcast_placement(num_tables=100, row_bytes=256, batch=64)
    assert res["concat_then_single_broadcast"] < res["host_broadcast"]
    assert res["concat_then_single_broadcast"] \
        <= res["device_broadcast_per_table"]


# ---- pipeline ------------------------------------------------------------

def test_pipeline_preserves_outputs():
    sparse = jax.jit(lambda x: x * 2.0)
    dense = jax.jit(lambda s, x: s + 1.0)
    pipe = TwoStagePipeline(lambda r: sparse(r), lambda s, r: dense(s, r))
    reqs = [jnp.full((4,), float(i)) for i in range(7)]
    outs, _ = pipe.run(reqs)
    outs_seq, _ = pipe.run_sequential(reqs)
    for a, b in zip(outs, outs_seq):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_steady_state_speedup_bounds():
    assert steady_state_speedup(1.0, 1.0) == pytest.approx(2.0)
    assert steady_state_speedup(1.0, 3.0) == pytest.approx(4.0 / 3.0)


# ---- metrics -------------------------------------------------------------

def test_ne_perfect_predictor_lower_than_base(rng):
    y = jnp.asarray(rng.integers(0, 2, 4096), jnp.float32)
    perfect = (y * 2 - 1) * 8.0
    ne = float(MET.normalized_entropy(perfect, y))
    assert ne < 0.1
    chance = jnp.zeros_like(y)
    assert float(MET.normalized_entropy(chance, y)) == pytest.approx(
        1.0, rel=0.05)


def test_cosine_similarity_self_is_one(key):
    a = jax.random.normal(key, (8, 64))
    assert float(MET.cosine_similarity(a, a)) == pytest.approx(1.0, abs=1e-5)


def test_token_agreement_counts_only_attributable_tokens():
    """Per pair, tokens count up to and including the FIRST mismatch:
    post-divergence tokens condition on different prefixes (greedy
    cascade) and must not dilute or inflate the metric."""
    assert MET.token_agreement([([1, 2, 3], [1, 2, 3])]) == 1.0
    # mismatch at position 1: counts 1 match + 1 miss, ignores the rest
    # (the trailing 9==9 "agreement" is a post-divergence coincidence)
    assert MET.token_agreement([([1, 5, 9], [1, 2, 9])]) \
        == pytest.approx(1 / 2)
    # first token wrong: one counted decision, zero matched
    assert MET.token_agreement([([7, 1, 1], [2, 1, 1])]) == 0.0
    # pools counted decisions across pairs: (3 + 1) matched / (3 + 2)
    assert MET.token_agreement([([1, 2, 3], [1, 2, 3]),
                                ([4, 0, 0], [4, 5, 0])]) \
        == pytest.approx(4 / 5)
    assert MET.token_agreement([]) == 1.0


# ---- numerics golden sets --------------------------------------------------

def test_golden_set_detects_regression(key):
    f = lambda x: x * 2.0
    g = GoldenSet.record(f, (jnp.arange(8.0),))
    ok, _ = g.check(f)
    assert ok
    ok, maxdiff = g.check(lambda x: x * 2.0 + 1e-2)
    assert not ok and maxdiff > 1e-3
