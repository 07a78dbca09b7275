"""The plain references against the engines at tiny widths on the CPU, and
their controls: the int8 and three-pass versions read worse than the
program."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, workload
from bench.drivers import dlrm as dlrm_drv
from bench.drivers import lm as lm_drv
from bench.reference import lm as ref_lm

LM = {"name": "tiny-lm", "num_hidden_layers": 2, "hidden_size": 64,
      "intermediate_size": 128, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
      "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "dtype": "float32",
      "engine": {"batch_slots": 4, "max_len": 64,
                 "prefill_buckets": [16, 32],
                 "attention_impl": "chunked_jnp", "precision": "fp32"}}
DLRM = {"name": "tiny-dlrm", "num_dense_features": 13, "embedding_dim": 16,
        "table_rows": [100 + 10 * i for i in range(6)],
        "avg_lookups_per_table": [1 + i % 4 for i in range(6)],
        "max_lookups_per_table": 8, "bottom_mlp": [32, 16],
        "top_mlp": [64, 32, 1], "table_block_rows": 64,
        "engine": {"partial_transfers": True, "step_group": 4,
                   "matmul_precision": "highest"},
        "check": {"requests": 2}}
MIX = {"items_per_request": 8, "bags": {"dist": "poisson_plus_one"},
       "ids": {"dist": "zipf", "alpha": 1.1}}


@pytest.fixture(scope="module")
def lm():
    from repro.models import model as model_mod
    from repro.serving.engine import InferenceEngine, Request
    dm = counts.Dims.of(LM)
    pcfg = lm_drv.program_config(LM)
    w = lm_drv.make_weights(jax.random.PRNGKey(3), dm, "float32")
    params = lm_drv.program_params(w, pcfg)
    eng = InferenceEngine(pcfg, params, batch_slots=4, max_len=64,
                          prefill_buckets=(16, 32))
    r = np.random.default_rng(0)
    reqs = [Request(i, r.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=6) for i, n in enumerate((5, 16, 23))]
    eng.run(reqs)
    return types.SimpleNamespace(w=w, params=params, pcfg=pcfg, reqs=reqs,
                                 model=model_mod)


def teacher_forced(lm, quant=None):
    S, R = 40, 6
    tokens = np.zeros((3, S), np.int32)
    read = np.zeros((3, R), np.int32)
    served = np.zeros((3, R), np.int32)
    for b, q in enumerate(lm.reqs):
        seq = np.concatenate([q.tokens, q.output[:-1]])
        tokens[b, :len(seq)] = seq
        read[b] = len(q.tokens) - 1 + np.arange(R)
        served[b] = q.output
    kw = dict(eps=1e-6, theta=10000.0)
    logits = ref_lm.logits_at(lm.w, tokens, read, **kw)
    out = {"logits": logits, "tokens": tokens, "read": read,
           "gaps": np.asarray(ref_lm.served_gaps(logits,
                                                 jnp.asarray(served)))}
    if quant:
        ctl = ref_lm.logits_at(lm.w, tokens, read, quant=quant, **kw)
        out["control"] = np.asarray(ref_lm.control_gaps(logits, ctl))
    return out


def test_lm_reference_matches_the_program_forward(lm):
    t = teacher_forced(lm)
    x, _, _ = lm.model.forward(lm.params, lm.pcfg,
                               {"tokens": jnp.asarray(t["tokens"])},
                               mode="full")
    head = lm.model.head_table(lm.params, lm.pcfg)
    prog = jnp.einsum("bsd,vd->bsv", x, head,
                      precision=jax.lax.Precision.HIGHEST)
    prog = np.take_along_axis(np.asarray(prog), t["read"][..., None], 1)
    np.testing.assert_allclose(np.asarray(t["logits"]), prog, atol=2e-4)


def test_lm_engine_tokens_are_the_reference_argmax(lm):
    assert float(teacher_forced(lm)["gaps"].max()) < 1e-4


def test_lm_int8_control_reads_worse(lm):
    t = teacher_forced(lm, quant="int8")
    assert float(t["control"].max()) > 100 * max(float(t["gaps"].max()),
                                                 1e-6)


@pytest.fixture
def dlrm_cell(jax_settings):
    ctx = types.SimpleNamespace(cfg=DLRM, traffic=MIX)
    cell = dlrm_drv.Cell(ctx)
    cell.build(11)
    recs = []
    for i in range(2):
        b = workload.dlrm_request(MIX, DLRM, 11, i)
        cell.engine.submit(b)
        out = cell.engine.step_once()
        recs.append(dlrm_drv.Rec(i, 0.0, b, done_t=0.0,
                                 scores=np.asarray(out[0])))
    return cell, {"recs": recs, "close": float("inf")}


def test_dlrm_reference_matches_the_engine(dlrm_cell):
    cell, win = dlrm_cell
    got = cell.check(11, win, control=True)
    assert got["requests"] == 2
    assert got["error"] < 1e-5
    assert got["control_error"] > 5 * got["error"]


def test_dlrm_reference_pools_live_rows_only():
    from bench.reference import dlrm as ref
    q = np.full((1, 1, 3, 2), 2, np.uint8)
    scale = np.full((1, 1, 3), 0.5, np.float16)
    bias = np.full((1, 1, 3), -1.0, np.float16)
    pooled = ref.pool(q, scale, bias, np.array([[2]]))
    np.testing.assert_array_equal(pooled, np.zeros((1, 1, 2)))
    pooled = ref.pool(q * 3, scale, bias, np.array([[2]]))
    np.testing.assert_array_equal(pooled, np.full((1, 1, 2), 4.0))
