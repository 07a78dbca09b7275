"""Jit'd wrapper + numerics registration for the w8a8 kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.numerics import OpValidationCase, register_op
from repro.kernels.w8a8.matmul import w8a8_matmul
from repro.kernels.w8a8.ref import w8a8_ref


@functools.partial(jax.jit, static_argnames=("interpret",))
def w8a8(xq, wq, x_scale, w_scale, *, interpret: Optional[bool] = None):
    """Dequantizing int8 matmul (compiled on the chip, interpreted on the
    CPU backend unless the caller pins ``interpret``)."""
    return w8a8_matmul(xq, wq, x_scale, w_scale, interpret=interpret)


def _mk(M, K, N, row_scale=False):
    def make(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        xq = jax.random.randint(k1, (M, K), -127, 128).astype(jnp.int8)
        wq = jax.random.randint(k2, (K, N), -127, 128).astype(jnp.int8)
        if row_scale:       # dynamic per-row activation scales
            xs = jax.random.uniform(k4, (M,), jnp.float32, 0.001, 0.05)
        else:
            xs = jnp.float32(0.02)
        ws = jax.random.uniform(k3, (N,), jnp.float32, 0.001, 0.02)
        return xq, wq, xs, ws
    return make


register_op(
    "w8a8_matmul", w8a8, w8a8_ref,
    # int32 accumulate is exact -> bitwise-comparable after dequant
    [OpValidationCase(f"{M}x{K}x{N}", _mk(M, K, N), rtol=1e-6, atol=1e-6)
     for (M, K, N) in [(128, 128, 128), (256, 512, 128), (128, 256, 384),
                       (512, 128, 256)]]
    # non-128-multiple serving bucket shapes (zero-padded to the tile
    # grid inside the kernel) and the per-row activation-scale path
    + [OpValidationCase("96x192x320_padded", _mk(96, 192, 320),
                        rtol=1e-6, atol=1e-6),
       OpValidationCase("48x160x288_rowscale_padded",
                        _mk(48, 160, 288, row_scale=True),
                        rtol=1e-6, atol=1e-6),
       OpValidationCase("128x128x128_rowscale",
                        _mk(128, 128, 128, row_scale=True),
                        rtol=1e-6, atol=1e-6)])
