"""Quantization (paper §V): property-based guarantees + workflow behavior."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.quantization import (dequantize_rows, dequantize_rows_int4,
                                     dequantize_rows_int8,
                                     quantization_workflow, quantize_act_int8,
                                     quantize_rows, quantize_rows_int4,
                                     quantize_rows_int8, quantize_weight_int8,
                                     w8a8_matmul_ref)


@settings(max_examples=25, deadline=None)
@given(rows=st.integers(1, 32), cols=st.sampled_from([2, 8, 16, 64]),
       seed=st.integers(0, 2**31 - 1), scale=st.floats(1e-3, 1e3))
def test_int8_rowwise_error_bound(rows, cols, seed, scale):
    """Round-trip error <= half a quantization step per element."""
    rng = np.random.default_rng(seed)
    t = jnp.asarray(rng.normal(size=(rows, cols)) * scale, jnp.float32)
    qt = quantize_rows_int8(t)
    deq = dequantize_rows_int8(qt)
    step = (t.max(axis=1) - t.min(axis=1)) / 255.0
    err = jnp.abs(deq - t).max(axis=1)
    # fp16 storage: scale err <= step*2^-11 (+ subnormal ulp 2^-25 when the
    # step is below fp16's min normal — found by hypothesis), bias err
    # <= |min|*2^-11
    slack = (255 * (step * 2.0 ** -11 + 2.0 ** -25)
             + jnp.abs(t.min(axis=1)) * 2.0 ** -10)
    assert bool(jnp.all(err <= step * 0.5 + slack + 1e-6)), \
        (np.asarray(err), np.asarray(step))


@settings(max_examples=25, deadline=None)
@given(rows=st.integers(1, 32), cols=st.sampled_from([2, 8, 64]),
       seed=st.integers(0, 2**31 - 1))
def test_int4_rowwise_error_bound(rows, cols, seed):
    rng = np.random.default_rng(seed)
    t = jnp.asarray(rng.normal(size=(rows, cols)), jnp.float32)
    qt = quantize_rows_int4(t)
    deq = dequantize_rows_int4(qt)
    step = (t.max(axis=1) - t.min(axis=1)) / 15.0
    err = jnp.abs(deq - t).max(axis=1)
    slack = (15 * (step * 2.0 ** -11 + 2.0 ** -25)
             + jnp.abs(t.min(axis=1)) * 2.0 ** -10)
    assert bool(jnp.all(err <= step * 0.5 + slack + 1e-6))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(4, 64),
       k=st.sampled_from([8, 32]))
def test_w8a8_quant_matmul_close_to_fp32(seed, n, k):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.1, jnp.float32)
    wq, wsc = quantize_weight_int8(w)
    xq, xsc = quantize_act_int8(x)
    got = w8a8_matmul_ref(xq, wq, xsc, wsc)
    want = x @ w
    # int8 x int8 with per-channel scales: ~1% relative error at these sizes
    denom = np.maximum(np.abs(np.asarray(want)), 1.0)
    assert (np.abs(np.asarray(got - want)) / denom).max() < 0.05


def test_int4_packing_roundtrip(key):
    t = jax.random.normal(key, (16, 8))
    qt = quantize_rows(t, 4)
    assert qt["q4"].shape == (16, 4)
    d = dequantize_rows(qt)
    assert d.shape == t.shape


def test_workflow_falls_back_worst_layer_first(key):
    """The paper's loop: highest-error layer -> fp16 until budget met."""
    ks = jax.random.split(key, 3)
    layers = {
        "fc_good": jax.random.normal(ks[0], (32, 32)) * 0.01,
        "fc_outlier": jax.random.normal(ks[1], (32, 32)).at[0, 0].set(100.0),
        "fc_mid": jax.random.normal(ks[2], (32, 32)),
    }

    def eval_metric(schemes):
        # synthetic: outlier layer in int8 costs 1e-3 NE, others 1e-5
        delta = 0.0
        for n, s in schemes.items():
            if s == "int8":
                delta += 1e-3 if n == "fc_outlier" else 1e-5
        return delta

    res = quantization_workflow(layers, eval_metric, budget=5e-4)
    assert res.passed
    schemes = {d.name: d.scheme for d in res.decisions}
    assert schemes["fc_outlier"] == "fp16"      # worst error fell back first
    assert schemes["fc_good"] == "int8"
    assert res.iterations == 1


def test_workflow_gives_up_gracefully(key):
    layers = {"a": jax.random.normal(key, (8, 8))}
    res = quantization_workflow(layers, lambda s: 1.0, budget=1e-4,
                                max_iters=3)
    assert not res.passed
    assert res.iterations <= 3


# ---- QuantizedParams build step (PR 6, serving w8a8) -----------------------

@pytest.fixture(scope="module")
def lm_smoke():
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import model as M
    cfg = reduce_for_smoke(get_config("deepseek-7b"))
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def test_build_quantized_params_site_and_leaf_contract(lm_smoke):
    """Every dense projection becomes a site; int8-decided sites carry
    exactly the {q8 int8 (reduction, out), scale f32 (out,)} leaf the
    w8a8 kernels consume (arrays only — the leaves must slice through
    jax.lax.scan like the fp32 originals)."""
    from repro.models.quantize import (QUANT_SITES, _collect_sites,
                                       build_quantized_params)
    cfg, params = lm_smoke
    sites = _collect_sites(params)
    per_block = sum(len(v) for v in QUANT_SITES.values())
    assert len(sites) == per_block * (len(params.get("scan", ()))
                                      + len(params.get("tail", ())))
    qp = build_quantized_params(cfg, params, budget=0.05)
    assert qp.quantized_sites + qp.fallback_sites == len(sites)
    assert qp.quantized_sites > 0
    assert float(qp.result.metric_delta) <= 0.05
    for name, scheme in qp.schemes.items():
        group, gi, mod, wname = \
            _collect_sites(params)[name]
        leaf = qp.params[group][int(gi)][mod][wname]
        if scheme == "int8":
            assert set(leaf) == {"q8", "scale"}
            assert leaf["q8"].dtype == jnp.int8
            assert leaf["scale"].dtype == jnp.float32
            # scan sites keep the stacked repeats dim in front
            extra = 1 if group == "scan" else 0
            assert leaf["q8"].ndim == 2 + extra
            assert leaf["scale"].ndim == 1 + extra
            assert leaf["q8"].shape[-1] == leaf["scale"].shape[-1]
        else:
            assert not isinstance(leaf, dict)      # fp32 original kept


def test_build_skip_list_substring_filters(lm_smoke):
    """skip=('wo',) force-keeps every output projection fp32 — substring
    match, exactly the core workflow's skip-list semantics."""
    from repro.models.quantize import build_quantized_params
    cfg, params = lm_smoke
    qp = build_quantized_params(cfg, params, skip=("wo",))
    assert qp.schemes
    assert not any(".wo" in name for name in qp.schemes)


def test_build_falls_back_under_impossible_budget(lm_smoke):
    """A budget no mix can meet drives the loop to fall sites back (paper
    §V: raise precision for high-error operators) and report not-passed
    instead of looping forever."""
    from repro.models.quantize import build_quantized_params
    cfg, params = lm_smoke
    qp = build_quantized_params(cfg, params, budget=-1.0, max_iters=2)
    assert not qp.result.passed
    assert qp.fallback_sites > 0
    assert qp.result.iterations <= 2


def test_build_on_siteless_arch_is_empty_not_an_error():
    """SSM mixers touch their weights directly, so a pure-Mamba stack has
    zero dense-projection sites — the build degrades to a no-op (all-fp32
    run params), it does not crash."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import model as M
    from repro.models.quantize import build_quantized_params
    cfg = reduce_for_smoke(get_config("mamba2-130m"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    qp = build_quantized_params(cfg, params)
    assert qp.quantized_sites == 0 and qp.fallback_sites == 0
    assert not qp.schemes
