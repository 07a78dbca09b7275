"""Pallas TPU kernel: w8a8 matmul — int8 x int8 -> int32 MXU accumulation
with a fused per-channel dequant epilogue (paper §V: int8 FC operators, 2x
the fp16 MXU throughput and half the weight bandwidth).

Tiling: (bm, bn) output tiles with a bk-deep reduction as the innermost grid
dimension; the int32 accumulator lives in a VMEM scratch and the epilogue
(scale multiply + cast) runs on the final k step. MXU-aligned 128x128x128
default tiles.

The activation scale is per-row (dynamic: one absmax scale per activation
row, the serving engine's w8a8 path) — a scalar scale broadcasts to every
row. Dims that are not block multiples are zero-padded up to the tile grid
(int8 zero padding is exact: padded rows/cols contribute zero partial sums
and are sliced off the output), so serving bucket shapes need no special
casing at the call site.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _w8a8_kernel(x_ref, w_ref, xs_ref, ws_ref, out_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        out_ref[...] = (acc_ref[...].astype(jnp.float32)
                        * xs_ref[...].astype(jnp.float32)
                        * ws_ref[...].astype(jnp.float32))


def _pad_dim(a, axis: int, to: int):
    if a.shape[axis] == to:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, to - a.shape[axis])
    return jnp.pad(a, widths)


def w8a8_matmul(xq, wq, x_scale, w_scale, *, bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: Optional[bool] = None):
    """xq (M,K) int8, wq (K,N) int8, x_scale scalar or (M,)/(M,1) f32
    (per-row activation scales), w_scale (N,) f32 -> (M,N) f32."""
    M, K = xq.shape
    K2, N = wq.shape
    assert K == K2
    xs = jnp.broadcast_to(jnp.asarray(x_scale, jnp.float32).reshape(-1, 1),
                          (M, 1))
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    # zero-pad up to the tile grid (exact for int8 inputs; scale padding is
    # arbitrary because the padded rows/cols are sliced off below)
    Mp, Np, Kp = (pl.cdiv(d, b) * b for d, b in
                  ((M, bm), (N, bn), (K, bk)))
    xq = _pad_dim(_pad_dim(xq, 0, Mp), 1, Kp)
    wq = _pad_dim(_pad_dim(wq, 0, Kp), 1, Np)
    xs = _pad_dim(xs, 0, Mp)
    ws = _pad_dim(w_scale.reshape(1, N), 1, Np)
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)
    out = pl.pallas_call(
        functools.partial(_w8a8_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(xq, wq, xs, ws)
    return out[:M, :N]
