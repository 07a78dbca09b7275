"""Plain DLRM forward in float64 numpy (arXiv:2107.04140, Fig. 2; the DLRM
of arXiv:1906.00091): each table's bag of int8 rows dequantized as
``q * scale + bias`` and summed over the bag's live entries, a ReLU bottom
MLP on the dense features, pairwise dot products of the bottom output and
the pooled embeddings (upper triangle, no diagonal), and a top MLP whose
last layer is linear.

It reads only the rows a request used, gathered by the benchmark from the
tables it made.

``precision="high"`` is the control: the configuration states float32 at
matmul precision ``highest``, and the nearest precision below it is
``high``, three bfloat16 passes. Every product of the MLPs and the
interaction is then taken as ``hi*hi + hi*lo + lo*hi`` of each operand's
bfloat16 high part and the bfloat16 rounding of its remainder, summed in
float32, as XLA's three-pass float32 matmul computes it.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import ml_dtypes
import numpy as np


def _split(x):
    """float32 x -> (hi, lo): bfloat16 parts, held in float32."""
    x = x.astype(np.float32)
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def _mm(spec: str, a, b, precision: Optional[str]):
    """einsum ``spec`` of a and b: float64, or three bfloat16 passes."""
    if precision is None:
        return np.einsum(spec, a.astype(np.float64), b.astype(np.float64))
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (np.einsum(spec, ah, bh) + np.einsum(spec, ah, bl)
            + np.einsum(spec, al, bh)).astype(np.float32)


def pool(q, scale, bias, lengths) -> np.ndarray:
    """q (B, T, L, D) uint8 rows, scale/bias (B, T, L), lengths (B, T) ->
    pooled (B, T, D) float64."""
    B, T, L, D = q.shape
    out = np.empty((B, T, D))
    live = np.arange(L)[None, :] < lengths[..., None]           # (B, T, L)
    for t in range(T):                   # one table at a time: bounded memory
        rows = q[:, t].astype(np.float64) \
            * scale[:, t].astype(np.float64)[..., None] \
            + bias[:, t].astype(np.float64)[..., None]
        out[:, t] = (rows * live[:, t, :, None]).sum(axis=1)
    return out


def scores(pooled: np.ndarray, dense: np.ndarray,
           bottom: Sequence[Tuple[np.ndarray, np.ndarray]],
           top: Sequence[Tuple[np.ndarray, np.ndarray]],
           precision: Optional[str] = None) -> np.ndarray:
    """One score per item: (B,)."""
    acc = np.float64 if precision is None else np.float32

    def mlp(layers, x, last_linear):
        for i, (w, b) in enumerate(layers):
            x = _mm("bi,io->bo", x, w, precision) + b.astype(acc)
            if not (last_linear and i == len(layers) - 1):
                x = np.maximum(x, 0.0)
        return x

    bot = mlp(bottom, dense, False)
    cat = np.concatenate([bot[:, None], pooled.astype(acc)], axis=1)
    n = cat.shape[1]
    iu, ju = np.triu_indices(n, k=1)
    inter = _mm("bid,bjd->bij", cat, cat, precision)[:, iu, ju]
    return mlp(top, np.concatenate([bot, inter], axis=1), True)[:, 0]


def worst_error(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest |got - ref| as a share of the largest |ref| of the request."""
    return float(np.max(np.abs(got.astype(np.float64) - ref))
                 / max(float(np.max(np.abs(ref))), 1e-30))
