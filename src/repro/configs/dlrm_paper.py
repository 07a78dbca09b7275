"""DLRM configs — the paper's centerpiece workload (Table I, Fig. 2/6).

The paper serves a "less complex" (70 GParams, 0.02 GFLOPs/batch) and a
"more complex" (>100 GParams, 0.1 GFLOPs/batch) recommendation model; both
are dominated by embedding tables (SLS) with a small dense MLP side. Their
widths are the repository's own reading of the paper (no public source
gives them).

Two interactions join the bottom MLP's output and the pooled bags:

- ``"dot"``: pairwise dot products (DLRM, arXiv:1906.00091), upper
  triangle without the diagonal, concatenated to the bottom output;
- ``"dcn"``: the low-rank cross network of DCN V2 (arXiv:2008.13535) over
  ``x0 = concat([bottom, pooled...])``, ``(T+1)*D`` wide:
  ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l`` for ``dcn_num_layers``
  layers of rank ``dcn_low_rank_dim``. MLPerf's DLRM-DCNv2 is the served
  model (``configs/dlrm_dcnv2.py``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Tuple

from repro.configs.base import QuantConfig


@dataclass(frozen=True)
class DLRMConfig:
    name: str
    num_dense_features: int
    # one entry per sparse feature (embedding table): number of rows
    table_rows: Tuple[int, ...]
    embed_dim: int
    # average lookups (bag size) per table — drives SLS load balancing (T8)
    avg_lookups_per_table: Tuple[int, ...]
    max_lookups_per_table: int          # static upper bound for compilation (T6)
    bottom_mlp: Tuple[int, ...]
    top_mlp: Tuple[int, ...]
    interaction: str = "dot"            # "dot" [52] or "dcn" (DCN V2)
    dcn_num_layers: int = 3             # cross layers ("dcn" only)
    dcn_low_rank_dim: int = 512         # rank of each cross layer ("dcn")
    quant: QuantConfig = field(default_factory=lambda: QuantConfig(
        embedding_bits=8, dense_int8=True))
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.interaction not in ("dot", "dcn"):
            raise ValueError(f"unknown interaction {self.interaction!r}")
        if self.interaction == "dcn" and self.bottom_mlp[-1] != self.embed_dim:
            raise ValueError("dcn concatenates the bottom output with the "
                             "pooled bags: bottom_mlp[-1] must be embed_dim")

    @property
    def num_tables(self) -> int:
        return len(self.table_rows)

    @property
    def cross_dim(self) -> int:
        """Width of the cross network's input, [bottom, pooled...]."""
        return (self.num_tables + 1) * self.embed_dim

    @property
    def top_in_dim(self) -> int:
        """Width of the top MLP's input."""
        if self.interaction == "dcn":
            return self.cross_dim
        n_int = self.num_tables + 1
        return self.bottom_mlp[-1] + n_int * (n_int - 1) // 2

    def embedding_params(self) -> int:
        return sum(self.table_rows) * self.embed_dim

    def dense_params(self) -> int:
        n = 0
        dims = (self.num_dense_features,) + self.bottom_mlp
        for a, b in zip(dims[:-1], dims[1:]):
            n += a * b + b
        if self.interaction == "dcn":       # V (no bias), W and b per layer
            n += self.dcn_num_layers * (
                2 * self.cross_dim * self.dcn_low_rank_dim + self.cross_dim)
        dims = (self.top_in_dim,) + self.top_mlp
        for a, b in zip(dims[:-1], dims[1:]):
            n += a * b + b
        return n

    def flops_per_sample(self) -> float:
        f = 0.0
        dims = (self.num_dense_features,) + self.bottom_mlp
        for a, b in zip(dims[:-1], dims[1:]):
            f += 2 * a * b
        n_int = self.num_tables + 1
        if self.interaction == "dcn":       # x @ V @ W in every layer
            f += self.dcn_num_layers * 4 * self.cross_dim \
                * self.dcn_low_rank_dim
        else:
            f += 2 * n_int * n_int * self.embed_dim     # interaction matmul
        dims = (self.top_in_dim,) + self.top_mlp
        for a, b in zip(dims[:-1], dims[1:]):
            f += 2 * a * b
        return f


def powerlaw_rows(num_tables: int, total_rows: int, alpha: float = 1.05,
                  min_rows: int = 1000) -> Tuple[int, ...]:
    """Deterministic power-law table-size profile (large head, long tail)."""
    weights = [1.0 / (i + 1) ** alpha for i in range(num_tables)]
    s = sum(weights)
    rows = [max(min_rows, int(total_rows * w / s)) for w in weights]
    return tuple(rows)


# Paper "less complex": ~70B params -> 64 tables, ~1.09B rows @ dim 64
PAPER_BASE = DLRMConfig(
    name="dlrm-paper-base",
    num_dense_features=13,
    table_rows=powerlaw_rows(64, 1_093_750_000),
    embed_dim=64,
    avg_lookups_per_table=tuple(1 + (i % 20) for i in range(64)),
    max_lookups_per_table=64,
    bottom_mlp=(512, 256, 64),
    top_mlp=(1024, 512, 256, 1),
)

# Paper "more complex" (the served 5x model): >100B params, ~5x dense GFLOPs
PAPER_COMPLEX = DLRMConfig(
    name="dlrm-paper-complex",
    num_dense_features=13,
    table_rows=powerlaw_rows(96, 1_171_875_000),
    embed_dim=96,
    avg_lookups_per_table=tuple(1 + (i * 7) % 40 for i in range(96)),
    max_lookups_per_table=128,
    bottom_mlp=(1024, 512, 96),
    top_mlp=(2048, 2048, 1024, 512, 1),
)


def reduce_for_smoke(cfg: DLRMConfig) -> DLRMConfig:
    """Tiny widths for CPU tests (8 tables of dim 16, cross rank 8); the
    chip serves published widths (``configs/dlrm_dcnv2.py``)."""
    n = min(cfg.num_tables, 8)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        table_rows=tuple(100 + 10 * i for i in range(n)),
        embed_dim=16,
        avg_lookups_per_table=tuple(1 + i % 4 for i in range(n)),
        max_lookups_per_table=8,
        bottom_mlp=(32, 16),
        top_mlp=(64, 32, 1),
        dcn_low_rank_dim=min(cfg.dcn_low_rank_dim, 8),
    )
