"""Least operations and bytes, from shapes. The numerators of every
utilisation and roofline share: what the work needs, never what a program
happens to do (padding, recomputation and masked lanes are not counted).

An LM is described by ``Dims`` (the configuration's sizes); a DLRM by its
configuration dict. Bytes assume the served types: bf16 weights, activations
and KV for the LM; int8 rows with an fp16 scale and bias for DLRM tables.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int          # hidden size
    heads: int
    kv_heads: int
    head_dim: int
    ff: int         # SwiGLU intermediate size
    vocab: int
    bytes_per: int = 2

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        return cls(cfg["num_hidden_layers"], cfg["hidden_size"],
                   cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"], cfg["intermediate_size"],
                   cfg["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.ff


def attn_pair_flops(dm: Dims) -> int:
    """QK^T and PV for one (query, key) pair in one layer."""
    return 4 * dm.heads * dm.head_dim


def prefill_flops(dm: Dims, n: int) -> int:
    """A causal prefill of n prompt tokens: every layer's projections for
    n tokens, attention over n(n+1)/2 pairs, and the head at the last
    position only (the one whose token is sampled)."""
    per_layer = 2 * dm.layer_matmul_params * n \
        + attn_pair_flops(dm) * n * (n + 1) // 2
    return dm.layers * per_layer + 2 * dm.d * dm.vocab


def decode_flops(dm: Dims, context: int) -> int:
    """One decoded token whose query attends ``context`` keys (itself
    included)."""
    return dm.layers * (2 * dm.layer_matmul_params
                        + attn_pair_flops(dm) * context) \
        + 2 * dm.d * dm.vocab


def kv_bytes_per_position(dm: Dims) -> int:
    return dm.layers * 2 * dm.kv_heads * dm.head_dim * dm.bytes_per


def decode_step_bytes(dm: Dims, contexts: Iterable[int]) -> int:
    """The least one decode step must read: every layer's weights and the
    head once, plus the live K/V of each decoding sequence."""
    weights = (dm.layers * dm.layer_matmul_params + dm.vocab * dm.d) \
        * dm.bytes_per
    return weights + sum(contexts) * kv_bytes_per_position(dm)


def flash_attn_least(dm: Dims, lengths: Sequence[int]):
    """One causal flash-attention call (one layer) over rows of the given
    real lengths: (flops, bytes) for Q, K, V read and O written once."""
    flops = sum(attn_pair_flops(dm) * n * (n + 1) // 2 for n in lengths)
    width = (2 * dm.heads + 2 * dm.kv_heads) * dm.head_dim
    return flops, sum(lengths) * width * dm.bytes_per


def roofline_time(flops: float, nbytes: float, peak_flops: float,
                  hbm_bytes_per_s: float):
    """(least seconds, which bound sets it)."""
    tf, tb = flops / peak_flops, nbytes / hbm_bytes_per_s
    return (tf, "compute") if tf >= tb else (tb, "memory")


# ---- DLRM -----------------------------------------------------------------

ROW_OVERHEAD_BYTES = 4      # fp16 scale + fp16 bias per int8 row


def sparse_least_bytes(cfg: dict, live_lookups: int, items: int) -> int:
    """Live lookups each read one int8 row with its scale and bias; the
    pooled float32 output (items x tables x dim) is written once."""
    D = cfg["embedding_dim"]
    T = len(cfg["table_rows"])
    return live_lookups * (D + ROW_OVERHEAD_BYTES) + items * T * D * 4


def dlrm_flops_per_sample(cfg: dict) -> float:
    """Bottom MLP, the pairwise-dot interaction as one (T+1)x(T+1) matrix
    product, and the top MLP: 2 flops per multiply-add."""
    f = 0.0
    dims = [cfg["num_dense_features"], *cfg["bottom_mlp"]]
    for a, b in zip(dims[:-1], dims[1:]):
        f += 2 * a * b
    n = len(cfg["table_rows"]) + 1
    f += 2 * n * n * cfg["embedding_dim"]
    dims = [cfg["bottom_mlp"][-1] + n * (n - 1) // 2, *cfg["top_mlp"]]
    for a, b in zip(dims[:-1], dims[1:]):
        f += 2 * a * b
    return f
