"""Jit'd public wrappers for the SLS kernels + numerics-validation cases
(paper §V-C: op-level unit tests against the reference implementation)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.numerics import OpValidationCase, register_op
from repro.kernels.sls import ref as sls_ref_mod
from repro.kernels.sls.sls import sls_int4_pallas, sls_int8_pallas, sls_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def sls(table, indices, lengths, *, interpret: Optional[bool] = None):
    return sls_pallas(table, indices, lengths, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sls_int8(q, scale, bias, indices, lengths, *,
             interpret: Optional[bool] = None):
    return sls_int8_pallas(q, scale, bias, indices, lengths,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sls_int4(q4, scale, bias, indices, lengths, *,
             interpret: Optional[bool] = None):
    return sls_int4_pallas(q4, scale, bias, indices, lengths,
                           interpret=interpret)


def _mk_fp(R, D, NB, L):
    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        table = jax.random.normal(k1, (R, D), jnp.float32)
        idx = jax.random.randint(k2, (NB, L), 0, R)
        lens = jax.random.randint(k3, (NB,), 0, L + 1)
        return table, idx, lens
    return make


def _mk_q(R, D, NB, L, bits):
    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        idx = jax.random.randint(k2, (NB, L), 0, R)
        lens = jax.random.randint(k3, (NB,), 0, L + 1)
        hi = 256 if bits == 8 else 16
        cols = D if bits == 8 else D // 2
        if bits == 4:
            q = jax.random.randint(k1, (R, cols), 0, 256).astype(jnp.uint8)
        else:
            q = jax.random.randint(k1, (R, cols), 0, hi).astype(jnp.uint8)
        scale = (jax.random.uniform(k1, (R,)) * 0.1 + 0.01).astype(jnp.float16)
        bias = (jax.random.normal(k2, (R,)) * 0.1).astype(jnp.float16)
        return q, scale, bias, idx, lens
    return make


register_op(
    "sls_fp32", sls, sls_ref_mod.sls_ref,
    [OpValidationCase(f"R{R}_D{D}_NB{NB}_L{L}", _mk_fp(R, D, NB, L),
                      rtol=1e-5, atol=1e-5)
     for (R, D, NB, L) in [(64, 16, 8, 4), (1000, 64, 32, 8),
                           (4096, 128, 16, 64), (128, 256, 4, 1)]])

register_op(
    "sls_int8", sls_int8, sls_ref_mod.sls_int8_ref,
    [OpValidationCase(f"R{R}_D{D}_NB{NB}_L{L}", _mk_q(R, D, NB, L, 8),
                      rtol=1e-4, atol=1e-4)
     for (R, D, NB, L) in [(64, 16, 8, 4), (1000, 64, 32, 8),
                           (512, 128, 16, 32)]])

register_op(
    "sls_int4", sls_int4, sls_ref_mod.sls_int4_ref,
    [OpValidationCase(f"R{R}_D{D}_NB{NB}_L{L}", _mk_q(R, D, NB, L, 4),
                      rtol=1e-4, atol=1e-4)
     for (R, D, NB, L) in [(64, 16, 8, 4), (1000, 64, 32, 8)]])
