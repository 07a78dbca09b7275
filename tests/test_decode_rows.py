"""Decode attention that reads the cache and writes only the new rows.

``attention.decode_attend`` attends over the cache as it stands plus the
new token's own K/V; ``attention.write_decode_rows`` then scatters the
new rows in place, and the model's decode step does that once for all
layers after the layer scan. The reference below is the straightforward
form of the same step: write the new K/V into the cache, then attend
over the written cache. Both must give the same outputs and the same
cache, and the engine's decode must stay token-identical to a full
forward over the same sequence.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models import attention as A
from repro.models import model as M
from repro.serving.engine import InferenceEngine, Request


def write_then_attend(p, x, cache, pos, cfg, kind, active):
    """Reference decode step: inactive rows re-write the value already at
    their write position, then every row attends its written cache."""
    B = x.shape[0]
    pos_b = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = A._project_qkv(p, x, cfg, pos_b[:, None])
    slots = cache["k"].shape[1]
    write_at = jnp.mod(pos_b, slots) if kind == "local" else pos_b
    new = {"k": k_new, "v": v_new}
    if "k_scale" in cache:
        kq, ks = A._kv_quant(k_new)
        vq, vs = A._kv_quant(v_new)
        new = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    out = {}
    for name, val in new.items():
        tail = (1,) * (val.ndim - 1)
        old = jnp.take_along_axis(cache[name], write_at.reshape((B,) + tail),
                                  axis=1).astype(val.dtype)
        val = jnp.where(active.reshape((B,) + tail), val, old)
        out[name] = jax.vmap(
            lambda c, v, at: jax.lax.dynamic_update_slice(
                c, v.astype(c.dtype), (at,) + (0,) * (c.ndim - 1)))(
                    cache[name], val, write_at)
    if "k_scale" in cache:
        ck = A._kv_dequant(out["k"], out["k_scale"], x.dtype)
        cv = A._kv_dequant(out["v"], out["v_scale"], x.dtype)
    else:
        ck, cv = out["k"], out["v"]
    idx = jnp.arange(slots)[None, :]
    valid = idx <= pos_b[:, None]
    if kind == "local":
        valid = jnp.where(pos_b[:, None] >= slots, True, valid)
    scores = A._gqa_scores(q, ck, cfg)
    scores = jnp.where(valid[:, None, None, None, :], scores,
                       jnp.asarray(A.NEG_INF, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return A._out_proj(p, A._gqa_out(probs, cv)), out


def _bf16_config(kv_int8):
    """gemma2 at smoke widths (window 8, logit softcap), bf16 like the
    served models, optionally with an int8 KV cache."""
    cfg = reduce_for_smoke(get_config("gemma2-27b"))
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16",
                              activation_dtype="bfloat16")
    if kv_int8:
        cfg = dataclasses.replace(
            cfg, quant=dataclasses.replace(cfg.quant, kv_cache_dtype="int8"))
    return cfg


def _filled_cache(cfg, kind, B, max_len, key):
    """A cache full of random values everywhere, so that a key the mask
    should hide changes the output if it leaks."""
    cache = A.init_kv_cache(cfg, B, max_len, kind)
    ks = jax.random.split(key, len(cache))
    out = {}
    for (name, leaf), k in zip(sorted(cache.items()), ks):
        if leaf.dtype == jnp.int8:
            out[name] = jax.random.randint(k, leaf.shape, -127, 128,
                                           jnp.int32).astype(jnp.int8)
        elif name.endswith("_scale"):
            out[name] = jax.random.uniform(k, leaf.shape, jnp.float32,
                                           0.002, 0.02).astype(leaf.dtype)
        else:
            out[name] = jax.random.normal(k, leaf.shape).astype(leaf.dtype)
    return out


MAX_LEN = 32
CASES = {
    # kind, int8 KV, per-slot positions (window 8 for local)
    "global": ("global", False, [0, 5, 17, 31]),
    "local_before_wrap": ("local", False, [0, 3, 6, 7]),
    "local_after_wrap": ("local", False, [8, 9, 15, 30]),
    "local_mixed": ("local", False, [2, 8, 7, 23]),
    "global_int8": ("global", True, [0, 5, 17, 31]),
    "local_int8_after_wrap": ("local", True, [2, 8, 13, 30]),
}
ACTIVE = {"all_active": [True, True, True, True],
          "some_inactive": [True, False, True, False]}


@pytest.mark.parametrize("active", list(ACTIVE), ids=list(ACTIVE))
@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_attend_then_write_matches_write_then_attend(case, active):
    kind, int8, pos = CASES[case]
    cfg = _bf16_config(int8)
    key = jax.random.PRNGKey(3)
    kp, kx, kc = jax.random.split(key, 3)
    B = len(pos)
    p = A.init_attention(cfg, kp)
    x = (jax.random.normal(kx, (B, 1, cfg.d_model)) * 0.5).astype(
        jnp.bfloat16)
    cache = _filled_cache(cfg, kind, B, MAX_LEN, kc)
    pos = jnp.asarray(pos, jnp.int32)
    act = jnp.asarray(ACTIVE[active])

    y_ref, c_ref = write_then_attend(p, x, cache, pos, cfg, kind, act)
    y, c_new = A.decode_attention(p, x, cache, pos, cfg, kind, active=act)

    # an inactive row's output is discarded (the reference attends the
    # old value at its write slot there, this path the new token)
    live = np.asarray(ACTIVE[active])
    np.testing.assert_allclose(np.asarray(y, np.float32)[live],
                               np.asarray(y_ref, np.float32)[live],
                               rtol=2e-2, atol=2e-2)
    assert set(c_new) == set(cache)
    for name in cache:
        got, ref, old = (np.asarray(t[name]) for t in (c_new, c_ref, cache))
        assert got.dtype == old.dtype and got.shape == old.shape
        for b in range(B):
            if ACTIVE[active][b]:
                np.testing.assert_array_equal(got[b], ref[b], err_msg=name)
            else:
                np.testing.assert_array_equal(got[b], old[b], err_msg=name)


def test_stacked_write_matches_per_layer_write():
    """One scatter over a stack of layers writes what per-layer writes
    do: the model's decode step writes the scan's rows this way."""
    cfg = _bf16_config(kv_int8=True)
    L, B = 3, 4
    key = jax.random.PRNGKey(5)
    caches = [_filled_cache(cfg, "local", B, MAX_LEN, k)
              for k in jax.random.split(key, L)]
    rows = [{n: c[n][:, :1] for n in c}
            for c in (_filled_cache(cfg, "local", B, MAX_LEN, k)
                      for k in jax.random.split(jax.random.PRNGKey(6), L))]
    pos = jnp.asarray([3, 9, 16, 30], jnp.int32)
    act = jnp.asarray([True, True, False, True])
    stack = lambda ts: jax.tree.map(lambda *a: jnp.stack(a), *ts)
    got = A.write_decode_rows(stack(caches), stack(rows), pos, "local", act)
    want = stack([A.write_decode_rows(c, r, pos, "local", act)
                  for c, r in zip(caches, rows)])
    for name in got:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))


# ---- engine: decode through the model's layer scan -------------------------

@pytest.mark.parametrize("arch", ["deepseek-7b", "gemma2-27b"])
def test_engine_decode_matches_full_forward(arch):
    """40 decode steps (gemma2's window-8 rings wrap five times) give the
    tokens a full forward over the same sequence picks greedily."""
    cfg = reduce_for_smoke(get_config(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    prompt = np.random.default_rng(7).integers(
        0, cfg.vocab_size, 6).astype(np.int32)
    req = Request(0, prompt, max_new_tokens=41)
    eng = InferenceEngine(cfg, params, batch_slots=2, max_len=64,
                          prefill_buckets=(8,))
    eng.run([req])
    assert len(req.output) == 41
    seq = np.concatenate([prompt, req.output[:-1]]).astype(np.int32)
    x, _, _ = M.forward(params, cfg, {"tokens": jnp.asarray(seq)[None]},
                        mode="full")
    picked = M.greedy_next(params, cfg, x[0, len(prompt) - 1:])
    assert np.asarray(picked).tolist() == list(req.output)


@pytest.mark.parametrize("arch", ["deepseek-7b", "gemma2-27b"])
def test_chunked_then_long_decode_matches_monolithic(arch):
    """Chunked prefill followed by a long decode gives the tokens that
    monolithic prefill followed by the same decode gives, with slots
    freed and refilled while others decode."""
    cfg = reduce_for_smoke(get_config(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    kw = dict(batch_slots=3, max_len=64, prefill_buckets=(8, 16, 32))

    def trace():
        rng = np.random.default_rng(9)
        return [Request(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                        max_new_tokens=m)
                for i, (n, m) in enumerate([(30, 20), (5, 24), (12, 9),
                                            (21, 16), (3, 30)])]

    mono, chunked = trace(), trace()
    InferenceEngine(cfg, params, **kw).run(mono)
    eng = InferenceEngine(cfg, params, prefill_chunk=8, **kw)
    eng.run(chunked)
    assert eng.telemetry.continuations > 0
    for a, b in zip(chunked, mono):
        assert len(a.output) == a.max_new_tokens
        assert a.output == b.output, a.rid
