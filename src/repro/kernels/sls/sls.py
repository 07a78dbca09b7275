"""Pallas TPU kernel: SLS (embedding-bag) with fused row-wise int8/int4
dequantization — the paper's dominant recommendation-model op (Table II),
executed on the accelerator's vector cores with tables in device memory.

TPU mapping: bag indices are SCALAR-PREFETCHED (SMEM) and drive the BlockSpec
index_map, so each grid step DMAs exactly one table row (1, D) HBM->VMEM —
the TPU analogue of the paper's 'simple lookup kernel' + partial-row traffic.
Accumulation happens in the revisited output block (VMEM-resident across the
inner grid dimension).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _sls_fp_kernel(idx_ref, len_ref, table_ref, out_ref, *, L: int):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(l < len_ref[b])
    def _acc():
        out_ref[...] += table_ref[...].astype(jnp.float32)


def _sls_int8_kernel(idx_ref, len_ref, q_ref, s_ref, b_ref, out_ref, *, L: int):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(l < len_ref[b])
    def _acc():
        row = q_ref[...].astype(jnp.float32)
        s = s_ref[0, 0].astype(jnp.float32)
        bia = b_ref[0, 0].astype(jnp.float32)
        out_ref[...] += row * s + bia


def _sls_int4_kernel(idx_ref, len_ref, q_ref, s_ref, b_ref, out_ref, *, L: int):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(l < len_ref[b])
    def _acc():
        packed = q_ref[...]                                   # (1, D//2) u8
        lo = (packed & 0xF).astype(jnp.float32)
        hi = (packed >> 4).astype(jnp.float32)
        row = jnp.stack([lo, hi], axis=-1).reshape(1, -1)     # (1, D)
        s = s_ref[0, 0].astype(jnp.float32)
        bia = b_ref[0, 0].astype(jnp.float32)
        out_ref[...] += row * s + bia


def _row_spec(L):
    return pl.BlockSpec((1, None),
                        lambda b, l, idx, lens: (idx[b * L + l], 0))


def _scalar_spec(L):
    return pl.BlockSpec((1, 1), lambda b, l, idx, lens: (idx[b * L + l], 0))


def sls_pallas(table, indices, lengths, *, interpret: Optional[bool] = None):
    """Float table. indices (NB, L), lengths (NB,) -> (NB, D) f32."""
    NB, L = indices.shape
    R, D = table.shape
    grid = (NB, L)
    return pl.pallas_call(
        functools.partial(_sls_fp_kernel, L=L),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[pl.BlockSpec((1, D),
                                   lambda b, l, idx, lens: (idx[b * L + l], 0))],
            out_specs=pl.BlockSpec((1, D), lambda b, l, idx, lens: (b, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((NB, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(indices.reshape(-1), lengths, table)


def sls_int8_pallas(q, scale, bias, indices, lengths, *,
                    interpret: Optional[bool] = None):
    """Row-wise int8 table with fused dequant. q (R,D) uint8; scale/bias (R,)."""
    NB, L = indices.shape
    R, D = q.shape
    grid = (NB, L)
    s2 = scale.reshape(R, 1)
    b2 = bias.reshape(R, 1)
    return pl.pallas_call(
        functools.partial(_sls_int8_kernel, L=L),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, D), lambda b, l, idx, lens: (idx[b * L + l], 0)),
                pl.BlockSpec((1, 1), lambda b, l, idx, lens: (idx[b * L + l], 0)),
                pl.BlockSpec((1, 1), lambda b, l, idx, lens: (idx[b * L + l], 0)),
            ],
            out_specs=pl.BlockSpec((1, D), lambda b, l, idx, lens: (b, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((NB, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(indices.reshape(-1), lengths, q, s2, b2)


def sls_int4_pallas(q4, scale, bias, indices, lengths, *,
                    interpret: Optional[bool] = None):
    """Packed int4 table (R, D//2) uint8 with fused unpack+dequant."""
    NB, L = indices.shape
    R, Dh = q4.shape
    grid = (NB, L)
    s2 = scale.reshape(R, 1)
    b2 = bias.reshape(R, 1)
    return pl.pallas_call(
        functools.partial(_sls_int4_kernel, L=L),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, Dh), lambda b, l, idx, lens: (idx[b * L + l], 0)),
                pl.BlockSpec((1, 1), lambda b, l, idx, lens: (idx[b * L + l], 0)),
                pl.BlockSpec((1, 1), lambda b, l, idx, lens: (idx[b * L + l], 0)),
            ],
            out_specs=pl.BlockSpec((1, 2 * Dh), lambda b, l, idx, lens: (b, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((NB, 2 * Dh), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(indices.reshape(-1), lengths, q4, s2, b2)
