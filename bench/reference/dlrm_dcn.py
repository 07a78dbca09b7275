"""Plain DLRM-DCNv2 forward in float64 numpy (MLPerf's DLRM-DCNv2:
mlcommons/training ``recommendation_v2/torchrec_dlrm``; DCN V2,
arXiv:2008.13535): the bags pooled as ``bench/reference/dlrm.py``'s
``pool`` pools them, a ReLU bottom MLP on the dense features, a low-rank
cross network over ``x0 = concat([bottom, pooled bags])``,

    x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l,

and a top MLP whose last layer is linear.

``precision="high"`` is the control, as in ``bench/reference/dlrm.py``:
every product of the MLPs and the cross layers in three bfloat16 passes,
summed in float32.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from bench.reference.dlrm import _mm, pool, worst_error  # noqa: F401

Layer = Tuple[np.ndarray, np.ndarray]


def scores(pooled: np.ndarray, dense: np.ndarray,
           bottom: Sequence[Layer], cross: Sequence[Tuple[np.ndarray, ...]],
           top: Sequence[Layer], precision: Optional[str] = None
           ) -> np.ndarray:
    """One score per item: (B,). ``cross`` holds (V, W, b) per layer."""
    acc = np.float64 if precision is None else np.float32

    def mlp(layers, x, last_linear):
        for i, (w, b) in enumerate(layers):
            x = _mm("bi,io->bo", x, w, precision) + b.astype(acc)
            if not (last_linear and i == len(layers) - 1):
                x = np.maximum(x, 0.0)
        return x

    bot = mlp(bottom, dense, False)
    x0 = np.concatenate([bot[:, None], pooled.astype(acc)], axis=1)
    x0 = x0.reshape(len(x0), -1)
    x = x0
    for v, w, b in cross:
        x = x0 * (_mm("br,rd->bd", _mm("bd,dr->br", x, v, precision), w,
                      precision) + b.astype(acc)) + x
    return mlp(top, x, True)[:, 0]
