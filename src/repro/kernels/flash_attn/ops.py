"""Jit'd wrapper + numerics registration for flash prefill/train attention.

``flash_attn`` pads S/T up to the block size (extra keys masked via lens,
extra queries sliced off) so arbitrary sequence lengths work.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.numerics import OpValidationCase, register_op
from repro.kernels.flash_attn.flash import flash_attention
from repro.kernels.flash_attn.ref import flash_attention_ref


MAX_Q_ROWS = 512


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "interpret"))
def flash_attn(q, k, v, lens=None, *, causal: bool = True, window: int = 0,
               softcap: float = 0.0, bq: int = 512, bk: int = 512,
               interpret: Optional[bool] = None):
    B, S = q.shape[:2]
    T = k.shape[1]
    # a q block holds G = H/K heads: cap its G*bq logit rows so the tile
    # fits VMEM (MQA at bq=512 would need 46 MB of a 16 MB scoped limit)
    G = q.shape[2] // k.shape[2]
    bq_ = min(bq, S, max(8, MAX_Q_ROWS // G // 8 * 8))
    Sp = -(-S // bq_) * bq_ if S % bq_ else S
    bk_ = min(bk, T)
    Tp = -(-T // bk_) * bk_ if T % bk_ else T
    if lens is None:
        lens = jnp.full((B,), T, jnp.int32)
    qp = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    out = flash_attention(qp, kp, vp, lens, causal=causal, window=window,
                          softcap=softcap, bq=bq_, bk=bk_,
                          interpret=interpret)
    return out[:, :S]


def _mk(B, S, H, K, hd, T=None, dtype=jnp.float32, lens_frac=None):
    T = T or S

    def make(key):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
        k = jax.random.normal(ks[1], (B, T, K, hd), dtype)
        v = jax.random.normal(ks[2], (B, T, K, hd), dtype)
        if lens_frac is None:
            return q, k, v
        lens = jnp.full((B,), max(int(T * lens_frac), 1), jnp.int32)
        return q, k, v, lens
    return make


_CASES = [
    # (name, maker, kwargs)
    ("mha_64", _mk(2, 64, 4, 4, 32), {}),
    ("gqa_128", _mk(2, 128, 8, 2, 64), {}),
    ("mqa_256", _mk(1, 256, 8, 1, 64), {}),
    ("local_128", _mk(2, 128, 4, 4, 32), {"window": 32}),
    ("softcap", _mk(2, 64, 4, 2, 32), {"softcap": 30.0}),
    ("padded_lens", _mk(2, 64, 4, 4, 32, lens_frac=0.6), {}),
    ("noncausal", _mk(2, 64, 4, 4, 32), {"causal": False}),
    ("odd_seq_96", _mk(1, 96, 4, 4, 32), {}),
    ("bf16", _mk(2, 128, 8, 2, 64, dtype=jnp.bfloat16), {}),
]

for name, maker, kw in _CASES:
    tol = 2e-2 if "bf16" in name else 2e-3
    register_op(
        f"flash_attn_{name}",
        functools.partial(flash_attn, bq=32, bk=32, **kw),
        functools.partial(flash_attention_ref, **kw),
        [OpValidationCase(name, maker, rtol=tol, atol=tol)])
