"""Jit'd wrapper + numerics registration for flash-decode."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.numerics import OpValidationCase, register_op
from repro.kernels.decode_attn.decode import flash_decode
from repro.kernels.decode_attn.ref import decode_attn_ref


@functools.partial(jax.jit,
                   static_argnames=("bs", "softcap", "interpret"))
def decode_attn(q, k, v, pos, *, bs: int = 512, softcap: float = 0.0,
                interpret: Optional[bool] = None):
    return flash_decode(q, k, v, pos, bs=bs, softcap=softcap,
                        interpret=interpret)


def _mk(B, H, K, hd, S, pos_frac, dtype=jnp.float32):
    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (B, H, hd), dtype)
        k_ = jax.random.normal(k2, (B, S, K, hd), dtype)
        v = jax.random.normal(k3, (B, S, K, hd), dtype)
        pos = jnp.int32(int(S * pos_frac))
        return q, k_, v, pos
    return make


register_op(
    "flash_decode",
    functools.partial(decode_attn, bs=64),
    decode_attn_ref,
    [OpValidationCase(f"B{B}_H{H}_K{K}_hd{hd}_S{S}_p{p}",
                      _mk(B, H, K, hd, S, p), rtol=2e-3, atol=2e-3)
     for (B, H, K, hd, S, p) in [
         (2, 8, 8, 64, 256, 0.5),      # MHA
         (2, 8, 2, 64, 256, 0.9),      # GQA
         (1, 8, 1, 128, 512, 0.3),     # MQA
         (4, 4, 4, 32, 64, 0.0),       # pos=0 edge
     ]])

register_op(
    "flash_decode_softcap",
    functools.partial(decode_attn, bs=64, softcap=50.0),
    functools.partial(decode_attn_ref, softcap=50.0),
    [OpValidationCase("B2_H8_K4_hd64_S256", _mk(2, 8, 4, 64, 256, 0.7),
                      rtol=2e-3, atol=2e-3)])


# ---- int8-KV variant (fused dequant in the block stream) -------------------

from repro.kernels.decode_attn.decode import flash_decode_int8
from repro.kernels.decode_attn.ref import decode_attn_int8_ref


@functools.partial(jax.jit, static_argnames=("bs", "softcap", "interpret"))
def decode_attn_int8(q, kq, k_scale, vq, v_scale, pos, *, bs: int = 512,
                     softcap: float = 0.0, interpret: Optional[bool] = None):
    return flash_decode_int8(q, kq, k_scale, vq, v_scale, pos, bs=bs,
                             softcap=softcap, interpret=interpret)


def _mk_int8(B, H, K, hd, S, pos_frac):
    def make(key):
        ks = jax.random.split(key, 5)
        q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
        kq = jax.random.randint(ks[1], (B, S, K, hd), -127, 128).astype(jnp.int8)
        vq = jax.random.randint(ks[2], (B, S, K, hd), -127, 128).astype(jnp.int8)
        k_scale = (jax.random.uniform(ks[3], (B, S, K)) * 0.02
                   + 0.001).astype(jnp.float16)
        v_scale = (jax.random.uniform(ks[4], (B, S, K)) * 0.02
                   + 0.001).astype(jnp.float16)
        pos = jnp.int32(int(S * pos_frac))
        return q, kq, k_scale, vq, v_scale, pos
    return make


register_op(
    "flash_decode_int8",
    functools.partial(decode_attn_int8, bs=64),
    decode_attn_int8_ref,
    [OpValidationCase(f"B{B}_H{H}_K{K}_hd{hd}_S{S}_p{p}",
                      _mk_int8(B, H, K, hd, S, p), rtol=2e-3, atol=2e-3)
     for (B, H, K, hd, S, p) in [
         (2, 8, 8, 64, 256, 0.5),
         (2, 8, 2, 64, 256, 0.9),      # GQA
         (1, 8, 1, 128, 512, 0.3),     # MQA
     ]])
