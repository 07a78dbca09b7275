"""Pallas TPU kernel: flash attention for prefill/training forward.

This removes the HLO 4-pass S^2 floor identified in the perf hillclimb
(EXPERIMENTS.md §Perf): in plain HLO, the (B,K,G,Sq,T) score block must
materialize between the QK dot, the softmax and the PV dot — ~35 GB/layer
at 32k context. Here the whole chain runs on VMEM tiles: HBM traffic is
just Q + K + V + O.

TPU mapping: grid (batch, kv-head, q-block, kv-block), innermost kv axis
sequential so the online-softmax state (m, l, acc) lives in VMEM scratch
per (G*Bq, hd) tile; K/V stream HBM->VMEM in (Bk, hd) blocks; the (G*Bq,
Bk) logits tile feeds the MXU twice (QK^T and PV). The wrapper lays Q out
head-major (B, K, G, S, hd) and K/V as (B, K, T, hd), so every block's last
two dims are (rows, hd) — the (8, 128) tiling the chip's compiler requires
(a (1, hd) slice out of a K-head axis is refused for K > 1). Causal/local
masks are resolved from block indices — fully-masked kv blocks are skipped
(the paper's "only the used prefix is ever read", T6, applied to the causal
frontier).

Supports GQA/MQA (G = H/K query heads per kv head), causal and
sliding-window masks, logit softcap, and a valid-length mask for padded
batches (scalar-prefetched per-row lengths).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, nk: int, causal: bool,
                  window: int, softcap: float, scale: float):
    b, h, qi, ki = (pl.program_id(i) for i in range(4))

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # block-level mask culling: skip kv blocks entirely above the causal
    # frontier or entirely left of the local window
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window > 0:
        live = jnp.logical_and(live, q_start - (k_start + bk - 1) < window) \
            if causal else (q_start - (k_start + bk - 1) < window)

    @pl.when(live)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)                # (G, Bq, hd)
        G, hd = q.shape[0], q.shape[2]
        rows = G * bq
        q2 = q.reshape(rows, hd)                           # row = g*Bq + i
        k = k_ref[0, 0].astype(jnp.float32)                # (Bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                      # (G*Bq, Bk)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        qpos = q_start + (r if G == 1 else jax.lax.rem(r, bq))
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        mask = kpos < lens_ref[b]                          # padded tail
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                # (G*Bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = o.reshape(o_ref.shape[2:]).astype(o_ref.dtype)


def flash_attention(q, k, v, lens=None, *, causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    bq: int = 512, bk: int = 512,
                    interpret: Optional[bool] = None):
    """q (B,S,H,hd); k,v (B,T,K,hd); lens (B,) valid kv length (default T).

    Returns (B,S,H,hd) in q.dtype. S % bq == 0 and T % bk == 0 required
    (the ops.py wrapper pads); H % K == 0 (GQA).
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    nq, nk = S // bq, T // bk
    if lens is None:
        lens = jnp.full((B,), T, jnp.int32)
    qg = q.reshape(B, S, K, G, hd).transpose(0, 2, 3, 1, 4)   # (B,K,G,S,hd)
    kt = k.transpose(0, 2, 1, 3)                               # (B,K,T,hd)
    vt = v.transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                          window=window, softcap=softcap,
                          scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, G, bq, hd),
                             lambda b, h, qi, ki, lens: (b, h, 0, qi, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, qi, ki, lens: (b, h, ki, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, qi, ki, lens: (b, h, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, bq, hd),
                                   lambda b, h, qi, ki, lens: (b, h, 0, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, S, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(lens, jnp.int32), qg, kt, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
