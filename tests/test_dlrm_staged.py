"""The sparse stage on the ingest's staging buffer: ``sls_forward`` on the
``(B, W)`` staged ids (``core/transfer.py``: each item's bags back to back
in table order, then padding) against the padded ``(B, T, L)`` bags, and
``DLRMEngine.serve`` against ``dlrm_forward`` on the padded bags."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs.dlrm_dcnv2 import SMOKE
from repro.core import transfer as TR
from repro.core.quantization import quantize_rows
from repro.data.synthetic import dlrm_batches
from repro.models import dlrm as D
from repro.serving.dlrm_engine import DLRMEngine

TOL = dict(rtol=1e-6, atol=1e-6)


@pytest.fixture(scope="module")
def smoke():
    cfg = SMOKE
    asn = D.make_assignment(cfg, 1)
    params = D.init_dlrm(cfg, asn, jax.random.PRNGKey(0))
    sls = jax.jit(lambda p, i, l: D.sls_forward(p, cfg, asn, i, l))
    return cfg, asn, params, sls


def _slab(params, kind):
    if kind == "float":
        return params
    q = quantize_rows(params["slab"], 8)
    return {**{k: v for k, v in params.items() if k != "slab"},
            "slab_q": D.pack_scale_bias(q) if kind == "int8_sb" else q}


def _poisson(cfg, rng):
    """dlrm_batches' bags (junk ids past each bag), each request cut to its
    own longest bag so that the requests span several width buckets."""
    out = []
    for s, cap in enumerate((1, 2, 3, 5, 8)):
        b = next(dlrm_batches(cfg, 8, seed=s))
        out.append((b["indices"], np.minimum(b["lengths"], cap)))
    return out


def _edges(cfg, rng):
    """Bags of length 1 and of the full L, a table whose used width is 1,
    and a table with no lookups at all."""
    B, T, L = 6, cfg.num_tables, cfg.max_lookups_per_table
    idx = np.stack([rng.integers(0, r, (B, L)) for r in cfg.table_rows],
                   axis=1).astype(np.int32)
    lens = rng.integers(1, L + 1, (B, T)).astype(np.int32)
    lens[:, 0] = 1                  # used width 1
    lens[:, 1] = 1
    lens[0, 1] = L                  # one full bag among bags of 1
    lens[:, 2] = L                  # every bag full
    lens[:, 3] = 0                  # no lookups
    return [(idx, lens)]


@pytest.mark.parametrize("bags", [_poisson, _edges])
@pytest.mark.parametrize("kind", ["float", "int8", "int8_sb"])
def test_staged_matches_padded(smoke, kind, bags):
    cfg, asn, params, sls = smoke
    params = _slab(params, kind)
    widths = set()
    for idx, lens in bags(cfg, np.random.default_rng(0)):
        staged, lens_dev = TR.staged_transfer(TR.SparseBatch(idx, lens))
        widths.add(staged.shape[1])
        np.testing.assert_allclose(np.asarray(sls(params, staged, lens_dev)),
                                   np.asarray(sls(params, idx, lens)), **TOL)
    if bags is _poisson:
        assert len(widths) >= 3


@pytest.mark.parametrize("kind", ["float", "int8_sb"])
def test_staged_padding_columns_are_not_read(smoke, kind):
    """Ids in the staging buffer past each item's lookups add nothing."""
    cfg, asn, params, sls = smoke
    params = _slab(params, kind)
    rng = np.random.default_rng(1)
    B, T, L = 6, cfg.num_tables, cfg.max_lookups_per_table
    idx = np.zeros((B, T, L), np.int32)
    lens = np.ones((B, T), np.int32)
    lens[:, :5] = [3, 1, 4, 1, 1]          # item 0: 13 lookups, width 16
    lens[1:, 0] = 0                        # the others shorter, each by
    lens[2:, 2] = np.arange(B - 2)         # its own number of columns
    for t in range(T):
        idx[:, t] = rng.integers(0, cfg.table_rows[t], (B, L))
    idx[np.arange(L)[None, None] >= lens[..., None]] = 0
    staged, lens_dev = TR.staged_transfer(TR.SparseBatch(idx, lens))
    assert staged.shape == (B, 16)
    junk = np.asarray(staged).copy()
    dead = np.arange(16)[None, :] >= lens.sum(axis=1)[:, None]
    assert dead[1:].any(axis=1).all()
    junk[dead] = rng.integers(0, 100, int(dead.sum()))
    np.testing.assert_allclose(np.asarray(sls(params, junk, lens_dev)),
                               np.asarray(sls(params, idx, lens)), **TOL)


def _ragged(rng, B, T, L):
    """Seeded bags, some of length 0; item 0 fills its width bucket
    exactly and is the longest."""
    lens = rng.integers(0, L + 1, (B, T)).astype(np.int32)
    lens[rng.random((B, T)) < 0.2] = 0
    fill = TR.staged_width(int(lens.sum(axis=1).max()), T * L)
    lens[0] = 0
    for t in range(T):                      # item 0: `fill` lookups
        lens[0, t] = min(L, fill - lens[0].sum())
    assert lens[0].sum() == fill
    idx = rng.integers(0, 50, (B, T, L)).astype(np.int32)
    return idx, lens


@pytest.mark.parametrize("B,T,L,seed", [(7, 5, 6, 0), (12, 9, 16, 1),
                                         (4, 26, 100, 2)])
def test_item_major_staging(B, T, L, seed):
    rng = np.random.default_rng(seed)
    idx, lens = _ragged(rng, B, T, L)
    stats = TR.TransferStats()
    staged, lens_dev = TR.staged_transfer(TR.SparseBatch(idx, lens), stats)
    W = staged.shape[1]
    assert W == TR.staged_width(int(lens.sum(axis=1).max()), T * L)
    assert W == lens[0].sum()
    table_major = int(lens.max(axis=0).sum())   # each table's longest bag
    assert W <= TR.staged_width(table_major, T * L)
    assert stats.rows_gathered == B * W and stats.rows_live == lens.sum()
    padded = np.where(np.arange(L) < lens[..., None], idx, 0)
    np.testing.assert_array_equal(
        np.asarray(TR.unpack_indices(staged, lens_dev, L)), padded)
    rows, dim = 50 * T, 16                  # rows drawn as init_dlrm does
    slab = jax.random.normal(jax.random.PRNGKey(seed), (rows, dim)) / 4
    offsets = jax.numpy.arange(0, rows, 50, dtype=np.int32)
    got = D._sls_staged(slab, offsets, staged, lens_dev)
    want = D._pool_rows(D._take_dequant(slab, padded + offsets[:, None]),
                        lens, L)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_engine_serves_what_dlrm_forward_gives(smoke):
    cfg, asn, params, _ = smoke
    params = _slab(params, "int8_sb")
    batches = [next(dlrm_batches(cfg, 16, seed=s)) for s in range(3)]
    outs, _ = DLRMEngine(cfg, asn, params).serve(batches)
    fwd = jax.jit(lambda p, x, i, l: D.dlrm_forward(p, cfg, asn, x, i, l))
    assert len(outs) == len(batches)
    for b, got in zip(batches, outs):
        want = fwd(params, b["dense"], b["indices"], b["lengths"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


_ROW_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.configs.dlrm_dcnv2 import SMOKE as cfg
    from repro.core import transfer as TR
    from repro.data.synthetic import dlrm_batches
    from repro.launch.mesh import make_mesh
    from repro.models import dlrm as D
    from repro.sharding.rules import use_mesh

    asn = D.make_assignment(cfg, 4)
    params = D.init_dlrm(cfg, asn, jax.random.PRNGKey(0), quantize=True)
    b = next(dlrm_batches(cfg, 16, seed=0))
    want = D.sls_forward(params, cfg, asn, b["indices"], b["lengths"])
    staged, lens = TR.staged_transfer(TR.SparseBatch(b["indices"],
                                                     b["lengths"]))
    with use_mesh(make_mesh((2, 2), ("data", "model"))):
        got = jax.jit(lambda p, i, l: D.sls_forward(p, cfg, asn, i, l))(
            params, staged, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    print("ROW_SHARDED_OK")
""")


def test_staged_ids_on_row_sharded_tables():
    """Under a mesh that shards the table rows, the staged ids are unpacked
    and pooled by the padded path: the same bags as without a mesh."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _ROW_SHARDED], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ROW_SHARDED_OK" in r.stdout
