"""Plain float32 forward of a DeepSeek-LLM (Llama-architecture) decoder.

Written from the published description (arXiv:2401.02954, Llama layout):
pre-RMSNorm blocks, multi-head attention with rotary embeddings in the
split-half convention, SwiGLU MLP, final RMSNorm and an untied head. No
cache, no kernels, no batching tricks: one causal pass over each whole
sequence, every matmul at ``Precision.HIGHEST`` in float32.

Weights come as a dict of stacked per-layer arrays (the benchmark makes them
from the seed), in any float type; each layer is upcast to float32 when it is
used, so the pass fits beside the served weights. Departure from the
description: a norm's gain is stored as ``s`` and applied as ``1 + s``.

``quant="int8"`` is the control: every projection (attention q, k, v, o,
the MLP and the head) takes per-output-channel symmetric int8 weights and
per-token symmetric int8 activations, as a w8a8 deployment would.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    """Symmetric int8 rounding along ``axis`` (scale = max|x| / 127)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) \
        / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _proj(x, w, quant):
    """x (..., a) @ w (a, b) in float32."""
    if quant == "int8":
        x = _q8(x, -1)
        w = _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + s)


def _rope(x, theta):
    """x (B, S, heads, hd) at positions 0..S-1, split-half rotation."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def _layer(x, w, l, *, eps, theta, quant):
    def get(k):
        return jax.lax.dynamic_index_in_dim(w[k], l, keepdims=False) \
            .astype(F32)

    B, S, d = x.shape
    wq, wk, wv, wo = get("wq"), get("wk"), get("wv"), get("wo")
    H, hd = wq.shape[1], wq.shape[2]
    K = wk.shape[1]
    h = _rms(x, get("attn_norm"), eps)
    q = _proj(h, wq.reshape(d, H * hd), quant).reshape(B, S, H, hd)
    k = _proj(h, wk.reshape(d, K * hd), quant).reshape(B, S, K, hd)
    v = _proj(h, wv.reshape(d, K * hd), quant).reshape(B, S, K, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(B, S, K, H // K, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v, precision=HI)
    x = x + _proj(o.reshape(B, S, H * hd), wo.reshape(H * hd, d), quant)
    h = _rms(x, get("mlp_norm"), eps)
    g = _proj(h, get("w_gate"), quant)
    u = _proj(h, get("w_up"), quant)
    return x + _proj(jax.nn.silu(g) * u, get("w_down"), quant)


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, read, final_norm, lm_head, *, eps, quant):
    xs = jnp.take_along_axis(x, read[..., None], axis=1)        # (B, R, d)
    h = _rms(xs, final_norm.astype(F32), eps)
    return _proj(h, lm_head.astype(F32).T, quant)               # (B, R, V)


def logits_at(w: dict, tokens: np.ndarray, read: np.ndarray, *, eps: float,
              theta: float, quant: Optional[str] = None):
    """Float32 logits (B, R, V) at positions ``read`` (B, R) of each row of
    ``tokens`` (B, S); a row's padding after its last real token does not
    reach earlier positions (the pass is causal)."""
    x = _embed(w["embed"], jnp.asarray(tokens, jnp.int32))
    layers = {k: w[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                "mlp_norm", "w_gate", "w_up", "w_down")}
    for l in range(w["wq"].shape[0]):
        x = _layer(x, layers, jnp.int32(l), eps=eps, theta=theta, quant=quant)
    return _head(x, jnp.asarray(read, jnp.int32), w["final_norm"],
                 w["lm_head"], eps=eps, quant=quant)


@jax.jit
def served_gaps(logits, tokens):
    """How far each served token's logit lies below the best one."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return best - got


@jax.jit
def control_gaps(logits, control_logits):
    """The gap, in the float32 logits, of the token the control ranks
    first."""
    pick = jnp.argmax(control_logits, -1)
    return served_gaps(logits, pick)
