"""DLRM with the DCN V2 cross network (MLPerf's DLRM-DCNv2) served through
``DLRMEngine``, against the float64 reference in
``bench/reference/dlrm_dcn.py``; the dot interaction unchanged bit for
bit; and the sparse stage compiled once per staged-width bucket."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench.drivers.common import CompileClock  # noqa: E402
from bench.reference import dlrm_dcn as ref  # noqa: E402
from repro.configs import dlrm_paper  # noqa: E402
from repro.configs.dlrm_dcnv2 import CONFIG, SMOKE  # noqa: E402
from repro.core import transfer as TR  # noqa: E402
from repro.data.synthetic import dlrm_batches  # noqa: E402
from repro.models import dlrm as D  # noqa: E402
from repro.serving.dlrm_engine import DLRMEngine  # noqa: E402

# float32 at matmul precision "highest" against float64: the widest score
# gap seen here is about 1e-7 of the largest score; three bfloat16 passes
# ("high") or a dropped cross term give 1e-5 and more
TOL = 2e-6


def _random_biases(params, key):
    """init_dlrm zeroes every bias; seeded random ones make b_l count."""
    leaves, tree = jax.tree.flatten_with_path(params)
    out = []
    for i, (path, x) in enumerate(leaves):
        if getattr(path[-1], "key", None) == "b":
            x = 0.1 * jax.random.normal(jax.random.fold_in(key, i), x.shape)
        out.append(x)
    return jax.tree.unflatten(tree, out)


def _serve(cfg, asn, params, batches):
    with jax.default_matmul_precision("highest"):
        eng = DLRMEngine(cfg, asn, params)
        for b in batches:
            eng.submit(b)
        outs = []
        while eng.has_work:
            outs += [np.asarray(o) for o in eng.step_once()]
    return outs


@pytest.fixture(scope="module")
def model():
    """SMOKE widths: 8 int8 tables of dim 16, 3 cross layers of rank 8,
    seeded weights and biases, and three requests of 16 items."""
    cfg = SMOKE
    asn = D.make_assignment(cfg, 1)
    params = D.init_dlrm(cfg, asn, jax.random.PRNGKey(0), quantize=True)
    params = {**_random_biases({k: v for k, v in params.items()
                                if k != "slab_q"}, jax.random.PRNGKey(1)),
              "slab_q": params["slab_q"]}
    batches = [next(dlrm_batches(cfg, 16, seed=s)) for s in range(3)]
    return cfg, asn, params, batches


def worst_vs_reference(cfg, asn, params, batches, outs) -> float:
    host = jax.device_get(params)
    slab = host["slab_q"]
    mlp = lambda k: [(l["w"], l["b"]) for l in host[k]]
    cross = [(l["v"], l["w"], l["b"]) for l in host["cross"]]
    worst = 0.0
    for b, got in zip(batches, outs):
        gidx = b["indices"] + np.asarray(asn.table_offset)[None, :, None]
        pooled = ref.pool(slab["q8"][gidx], slab["scale"][gidx],
                          slab["bias"][gidx], b["lengths"])
        want = ref.scores(pooled, b["dense"], mlp("bottom"), cross,
                          mlp("top"))
        worst = max(worst, ref.worst_error(got, want))
    return worst


def test_engine_matches_reference(model):
    outs = _serve(*model)
    assert len(outs) == len(model[3])
    assert worst_vs_reference(*model, outs) < TOL


def _cross_without(term):
    def cross(layers, x0):
        x = x0
        for l in layers:
            y = (x @ l["v"]) @ l["w"] + (0 if term == "bias" else l["b"])
            x = (y if term == "x0" else x0 * y) + x
        return x
    return cross


@pytest.mark.parametrize("term", ["x0", "bias"])
def test_a_dropped_cross_term_fails(model, monkeypatch, term):
    monkeypatch.setattr(D, "cross_forward", _cross_without(term))
    outs = _serve(*model)
    assert worst_vs_reference(*model, outs) > 100 * TOL


def test_one_cross_layer_at_published_widths():
    """d = 27 x 128 = 3456, rank 512, on a few items: x0 (V x0 W + b) + x0
    against float64."""
    d, r = CONFIG.cross_dim, CONFIG.dcn_low_rank_dim
    assert (d, r) == (3456, 512)
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    x0 = jax.random.normal(k[0], (4, d))
    layer = {"v": jax.random.normal(k[1], (d, r)) / np.sqrt(d),
             "w": jax.random.normal(k[2], (r, d)) / np.sqrt(r),
             "b": 0.1 * jax.random.normal(k[3], (d,))}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(D.cross_forward)([layer], x0))
    x, v, w, b = (np.asarray(a, np.float64) for a in
                  (x0, layer["v"], layer["w"], layer["b"]))
    want = x * (x @ v @ w + b) + x
    assert np.abs(got - want).max() / np.abs(want).max() < TOL


# ---- the dot interaction, as it was before the cross network -----------

def _dot_dense_forward(params, dense_x, pooled):
    """dense_forward's dot path as written before ``dcn`` existed."""
    bot = D._mlp_apply(params["bottom"], dense_x.astype(jnp.float32))
    cat = jnp.concatenate([bot[:, None, :], pooled], axis=1)
    Z = jnp.einsum("bid,bjd->bij", cat, cat)
    n = cat.shape[1]
    iu, ju = np.triu_indices(n, k=1)
    top_in = jnp.concatenate([bot, Z[:, iu, ju]], axis=1)
    return D._mlp_apply(params["top"], top_in, final_linear=True)[:, 0]


@pytest.mark.parametrize("shards,quantize", [(1, True), (4, False)])
def test_dot_logits_bit_identical(shards, quantize):
    cfg = dlrm_paper.reduce_for_smoke(dlrm_paper.PAPER_BASE)
    asn = D.make_assignment(cfg, shards)
    params = D.init_dlrm(cfg, asn, jax.random.PRNGKey(0), quantize=quantize)
    assert set(params) == {"slab_q" if quantize else "slab", "bottom",
                           "top"}
    n = cfg.num_tables + 1
    assert params["top"][0]["w"].shape[0] \
        == cfg.bottom_mlp[-1] + n * (n - 1) // 2
    b = {k: jnp.asarray(v) for k, v in
         next(dlrm_batches(cfg, 32, seed=3)).items()}
    pooled = D.sls_forward(params, cfg, asn, b["indices"], b["lengths"])
    now = jax.jit(lambda p, x, q: D.dense_forward(p, cfg, x, q))
    np.testing.assert_array_equal(
        np.asarray(now(params, b["dense"], pooled)),
        np.asarray(jax.jit(_dot_dense_forward)(params, b["dense"], pooled)))


def test_dcn_counts():
    assert CONFIG.flops_per_sample() == 32_060_928
    d, r = 3456, 512
    mlp = lambda dims: sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    assert CONFIG.dense_params() == mlp((13, 512, 256, 128)) \
        + 3 * (2 * d * r + d) + mlp((d, 1024, 1024, 512, 256, 1))
    assert sum(CONFIG.table_rows) == 204_184_588
    assert sum(CONFIG.avg_lookups_per_table) == 214
    with pytest.raises(ValueError):
        dataclasses.replace(SMOKE, bottom_mlp=(32, 8))


def test_packed_scale_bias_reads_the_same_rows():
    """The engine's 256-byte scale-and-bias rows give the values the two
    element gathers give, bit for bit, on a row count that is not a
    multiple of 64."""
    from repro.core.quantization import quantize_rows
    table = jax.random.normal(jax.random.PRNGKey(3), (1000, 16))
    slab = quantize_rows(table, 8)
    packed = D.pack_scale_bias(slab)
    assert packed["sb"].shape == (16, 128)
    idx = jax.random.randint(jax.random.PRNGKey(4), (5, 3, 7), 0, 1000)
    np.testing.assert_array_equal(np.asarray(D._take_dequant(packed, idx)),
                                  np.asarray(D._take_dequant(slab, idx)))


# ---- ingest: the staged ids, one sparse compile per width bucket ---------

def _staged(batch):
    """Each item's bags back to back in table order, then zeros to the
    width bucket of the longest item."""
    B, T, L = batch.indices.shape
    items = [np.concatenate([batch.indices[b, t, :batch.lengths[b, t]]
                             for t in range(T)]) for b in range(B)]
    W = TR.staged_width(max(len(i) for i in items), T * L)
    return np.stack([np.pad(i, (0, W - len(i))) for i in items])


def _bags(rng, B, T, L, rows):
    """Poisson bags, each request with its own longest bag (1 to L)."""
    lens = np.minimum(rng.poisson(rng.uniform(0, L, T), (B, T)) + 1,
                      rng.integers(1, L + 1))
    idx = rng.integers(0, rows, (B, T, L)).astype(np.int32)
    idx[np.arange(L)[None, None] >= lens[..., None]] = 0
    return idx, lens.astype(np.int32)


def test_ingests_compile_nothing_after_the_first(model):
    cfg, asn, params, _ = model
    B, T, L = 12, cfg.num_tables, cfg.max_lookups_per_table
    eng = DLRMEngine(cfg, asn, params)
    rng = np.random.default_rng(5)
    want = TR.TransferStats()
    widths, per_request = set(), []
    with CompileClock() as cc:
        for _ in range(20):
            idx, lens = _bags(rng, B, T, L, min(cfg.table_rows))
            c0 = cc.count
            got = eng.ingest({"indices": idx, "lengths": lens,
                              "dense": np.zeros((B, 13), np.float32)})
            pooled = eng.executor.dispatch("sparse", (), eng._build_sparse,
                                           eng.params, *got["sls"])
            pooled.block_until_ready()
            per_request.append(cc.count - c0)
            sb = TR.SparseBatch(idx, lens)
            np.testing.assert_array_equal(np.asarray(got["sls"][0]),
                                          _staged(sb))
            np.testing.assert_array_equal(np.asarray(got["sls"][1]), lens)
            np.testing.assert_allclose(
                np.asarray(pooled),
                np.asarray(D.sls_forward(eng.params, cfg, asn, idx, lens)),
                rtol=1e-6, atol=1e-6)
            live = int(lens.sum())
            W = TR.staged_width(int(lens.sum(axis=1).max()), T * L)
            widths.add(W)
            # live ids and lengths shipped, the rest of the buffer padding
            want.bytes_full += idx.nbytes + lens.nbytes
            want.bytes_partial += live * 4 + lens.nbytes
            want.bytes_padding += (B * W - live) * 4
            want.num_transfers_naive += T + 1
            want.num_transfers_batched += 2
            want.rows_gathered += B * W
            want.rows_live += live
    assert len(widths) > 2                  # the bags did change bucket
    assert per_request[0] > 0 and per_request[1:] == [0] * 19
    assert eng.transfer_stats == want


def test_width_buckets():
    assert TR.width_buckets(48) == (1, 2, 4, 8, 16, 32, 48)
    assert [TR.staged_width(c, 48) for c in (0, 1, 3, 16, 17, 40, 48)] \
        == [1, 1, 4, 16, 32, 48, 48]
    assert set(TR.staged_width(c, 2600) for c in range(2601)) \
        == set(TR.width_buckets(2600))
