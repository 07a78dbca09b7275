"""Paper §V quantization: row-wise int8/int4 embedding tables, per-channel
w8a8 dense quantization, and the iterative accuracy-driven workflow
(quantize compute-heavy ops; fall back to fp16 via a skip-list when
per-layer error exceeds the budget).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Row-wise embedding-table quantization (paper: int8 + int4 mixed [18])
# --------------------------------------------------------------------------

def quantize_rows_int8(table: jax.Array) -> Dict[str, jax.Array]:
    """Asymmetric row-wise int8: q = round((x - min) / scale), scale/bias fp16
    per row (FBGEMM fused-rowwise layout)."""
    t = table.astype(jnp.float32)
    mn = jnp.min(t, axis=1, keepdims=True)
    mx = jnp.max(t, axis=1, keepdims=True)
    scale = jnp.maximum(mx - mn, 1e-8) / 255.0
    q = jnp.clip(jnp.round((t - mn) / scale), 0, 255).astype(jnp.uint8)
    # precision is encoded in the key name ('q8'/'q4') so the pytree stays
    # jit-friendly (no static ints as leaves)
    return {"q8": q, "scale": scale[:, 0].astype(jnp.float16),
            "bias": mn[:, 0].astype(jnp.float16)}


def dequantize_rows_int8(qt: Dict[str, jax.Array]) -> jax.Array:
    return (qt["q8"].astype(jnp.float32)
            * qt["scale"].astype(jnp.float32)[:, None]
            + qt["bias"].astype(jnp.float32)[:, None])


def quantize_rows_int4(table: jax.Array) -> Dict[str, jax.Array]:
    """Row-wise int4, two values packed per uint8 (even dim required)."""
    t = table.astype(jnp.float32)
    assert t.shape[1] % 2 == 0, "int4 packing needs even embed dim"
    mn = jnp.min(t, axis=1, keepdims=True)
    mx = jnp.max(t, axis=1, keepdims=True)
    scale = jnp.maximum(mx - mn, 1e-8) / 15.0
    q = jnp.clip(jnp.round((t - mn) / scale), 0, 15).astype(jnp.uint8)
    packed = (q[:, 0::2] | (q[:, 1::2] << 4)).astype(jnp.uint8)
    return {"q4": packed, "scale": scale[:, 0].astype(jnp.float16),
            "bias": mn[:, 0].astype(jnp.float16)}


def dequantize_rows_int4(qt: Dict[str, jax.Array]) -> jax.Array:
    lo = (qt["q4"] & 0xF).astype(jnp.float32)
    hi = (qt["q4"] >> 4).astype(jnp.float32)
    q = jnp.stack([lo, hi], axis=-1).reshape(qt["q4"].shape[0], -1)
    return (q * qt["scale"].astype(jnp.float32)[:, None]
            + qt["bias"].astype(jnp.float32)[:, None])


def quantize_rows(table: jax.Array, bits: int) -> Dict[str, jax.Array]:
    if bits == 8:
        return quantize_rows_int8(table)
    if bits == 4:
        return quantize_rows_int4(table)
    raise ValueError(f"unsupported embedding bits {bits}")


def dequantize_rows(qt: Dict[str, jax.Array]) -> jax.Array:
    return (dequantize_rows_int8 if "q8" in qt else dequantize_rows_int4)(qt)


# --------------------------------------------------------------------------
# Dense w8a8 (per-output-channel weight scales, per-tensor activation scale)
# --------------------------------------------------------------------------

def quantize_weight_int8(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """w (in, out) -> (int8 w, per-out-channel scale fp32), symmetric."""
    absmax = jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0), 1e-8)
    scale = absmax / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def quantize_act_int8(x: jax.Array,
                      scale: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Dynamic per-tensor activation quant (paper §VIII: dynamic quantization
    avoids static activation profiling)."""
    if scale is None:
        absmax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-8)
        scale = absmax / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def quantize_act_int8_rowwise(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dynamic per-ROW activation quant: one symmetric absmax scale per
    activation row (last axis = the GEMM reduction dim). Returns
    (int8 x, f32 scales of shape x.shape[:-1]) — the serving engines'
    w8a8 path, tighter than the per-tensor scale when rows differ in
    magnitude (e.g. a prefill batch mixing prompts)."""
    xf = x.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8)
    scale = absmax / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def w8a8_matmul_ref(xq: jax.Array, wq: jax.Array, x_scale, w_scale):
    """int8 x int8 -> int32 accumulate, dequant epilogue (pure-jnp oracle)."""
    acc = jnp.einsum("...i,io->...o", xq.astype(jnp.int32),
                     wq.astype(jnp.int32))
    return acc.astype(jnp.float32) * x_scale * w_scale


def is_quantized_dense(w) -> bool:
    """A dense projection leaf replaced by its w8a8 form: {"q8": int8
    (in, out), "scale": f32 (out,)} (embedding-table row quant also uses
    "q8" but carries a "bias")."""
    return isinstance(w, dict) and "q8" in w and "bias" not in w


def dense_w8a8(x: jax.Array, qw: Dict[str, jax.Array]) -> jax.Array:
    """Quantized dense apply: x (..., K) f32 times a quantized weight
    {"q8": (K, N) int8, "scale": (N,) f32} -> (..., N) f32, with dynamic
    per-row activation scales. On TPU the GEMM runs through the
    kernels/w8a8 Pallas kernel (int8 MXU path); elsewhere the bitwise-
    identical int32 einsum oracle keeps numerics exact without paying the
    kernel interpreter."""
    xq, xs = quantize_act_int8_rowwise(x)
    q8, w_scale = qw["q8"], qw["scale"].astype(jnp.float32)
    if jax.default_backend() == "tpu" and x.ndim >= 2:
        from repro.kernels.w8a8.matmul import w8a8_matmul
        K, N = q8.shape
        y = w8a8_matmul(xq.reshape(-1, K), q8, xs.reshape(-1), w_scale)
        return y.reshape(x.shape[:-1] + (N,)).astype(x.dtype)
    acc = jnp.einsum("...k,kn->...n", xq.astype(jnp.int32),
                     q8.astype(jnp.int32))
    y = acc.astype(jnp.float32) * xs[..., None] * w_scale
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# Quantization workflow (paper §V-B): iterative precision search with
# per-layer error feedback and an accuracy budget.
# --------------------------------------------------------------------------

@dataclass
class LayerQuantDecision:
    name: str
    scheme: str                 # 'int8' | 'fp16' (fallback)
    error: float                # relative per-layer error observed


@dataclass
class QuantWorkflowResult:
    decisions: List[LayerQuantDecision]
    passed: bool
    metric_delta: float
    iterations: int


def quantization_workflow(
        layers: Dict[str, jax.Array],
        eval_metric: Callable[[Dict[str, str]], float],
        *,
        budget: float,
        layer_error_fn: Optional[Callable[[str, jax.Array], float]] = None,
        max_iters: int = 8) -> QuantWorkflowResult:
    """Iteratively int8-quantize ``layers``; while the end metric delta
    exceeds ``budget``, move the highest-error layer back to fp16 (the paper:
    "use the per-layer quantization error as feedback and increase precision
    for operators that incur high quantization errors").

    ``eval_metric(schemes)`` returns the end-to-end metric degradation for a
    {layer: scheme} assignment (e.g. NE delta for DLRM).
    """
    def default_err(name, w):
        qw, s = quantize_weight_int8(w)
        deq = qw.astype(jnp.float32) * s
        num = jnp.linalg.norm(w.astype(jnp.float32) - deq)
        den = jnp.maximum(jnp.linalg.norm(w.astype(jnp.float32)), 1e-8)
        return float(num / den)

    err_fn = layer_error_fn or default_err
    errors = {n: err_fn(n, w) for n, w in layers.items()}
    schemes = {n: "int8" for n in layers}
    delta = float(eval_metric(schemes))
    iters = 0
    order = sorted(errors, key=lambda n: -errors[n])
    while delta > budget and iters < max_iters:
        # fall back the worst remaining int8 layer
        int8_left = [n for n in order if schemes[n] == "int8"]
        if not int8_left:
            break
        schemes[int8_left[0]] = "fp16"
        delta = float(eval_metric(schemes))
        iters += 1
    decisions = [LayerQuantDecision(n, schemes[n], errors[n])
                 for n in sorted(layers)]
    return QuantWorkflowResult(decisions, delta <= budget, delta, iters)
