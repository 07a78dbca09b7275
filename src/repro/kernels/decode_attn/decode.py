"""Pallas TPU kernel: flash-decode — one query token against a long KV cache
with online-softmax accumulation over sequence blocks.

This is the serving hot loop for decode_32k / long_500k. TPU mapping: the
cache streams HBM->VMEM in (bs, hd) blocks; running (m, l, acc) live in VMEM
scratch per (batch, kv-head); GQA query heads for one KV head form the
(G, hd) tile fed to the MXU. The current length ``pos`` is scalar-prefetched
so block validity is resolved without host round trips (paper T6 analogue:
only the used prefix of the static-size cache is ever read — grid blocks
past ``pos`` are masked, and their work is skipped).
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


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs: int, ns: int,
                   softcap: float):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0]
    start = si * bs
    # skip blocks entirely past the valid prefix
    @pl.when(start <= pos)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)               # (G, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)         # (bs, hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (q.shape[-1] ** -0.5)                     # (G, bs)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        valid = (start + jax.lax.iota(jnp.int32, bs)) <= pos
        s = jnp.where(valid[None, :], s, NEG_INF)
        m_prev = m_ref[...]                               # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid[None, :], p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def flash_decode(q, k, v, pos, *, bs: int = 512, softcap: float = 0.0,
                 interpret: Optional[bool] = None):
    """q (B,H,hd); k,v (B,S,K,hd); pos () int32 -> (B,H,hd) f32."""
    B, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    ns = S // bs
    qg = q.reshape(B, K, G, hd)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, ns=ns, softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, ns),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, s, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, bs, 1, hd), lambda b, h, s, pos: (b, s, h, 0)),
                pl.BlockSpec((1, bs, 1, hd), lambda b, h, s, pos: (b, s, h, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, s, pos: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(pos, jnp.int32).reshape(1), qg, k, v)
    return out.reshape(B, H, hd)


def _decode_int8_kernel(pos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, bs: int, ns: int,
                        softcap: float):
    """int8-KV variant: cache blocks stream as int8 + per-token scales and
    dequantize in VMEM (the bandwidth saving of the int8 KV cache is only
    real if the dequant happens after the HBM read — same fusion the sls
    int8 kernel uses)."""
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0]
    start = si * bs

    @pl.when(start <= pos)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)               # (G, hd)
        ks = ks_ref[0, :, 0].astype(jnp.float32)          # (bs,)
        vs = vs_ref[0, :, 0].astype(jnp.float32)
        k = k_ref[0, :, 0, :].astype(jnp.float32) * ks[:, None]
        v = v_ref[0, :, 0, :].astype(jnp.float32) * vs[:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (q.shape[-1] ** -0.5)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        valid = (start + jax.lax.iota(jnp.int32, bs)) <= pos
        s = jnp.where(valid[None, :], s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid[None, :], p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def flash_decode_int8(q, kq, k_scale, vq, v_scale, pos, *, bs: int = 512,
                      softcap: float = 0.0, interpret: Optional[bool] = None):
    """q (B,H,hd); kq,vq (B,S,K,hd) int8; *_scale (B,S,K) fp16;
    pos () int32 -> (B,H,hd) f32."""
    B, H, hd = q.shape
    S, K = kq.shape[1], kq.shape[2]
    G = H // K
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    ns = S // bs
    qg = q.reshape(B, K, G, hd)
    out = pl.pallas_call(
        functools.partial(_decode_int8_kernel, bs=bs, ns=ns, softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, ns),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, s, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, bs, 1, hd), lambda b, h, s, pos: (b, s, h, 0)),
                pl.BlockSpec((1, bs, 1), lambda b, h, s, pos: (b, s, h)),
                pl.BlockSpec((1, bs, 1, hd), lambda b, h, s, pos: (b, s, h, 0)),
                pl.BlockSpec((1, bs, 1), lambda b, h, s, pos: (b, s, h)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, s, pos: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(pos, jnp.int32).reshape(1), qg, kq, k_scale, vq, v_scale)
    return out.reshape(B, H, hd)
