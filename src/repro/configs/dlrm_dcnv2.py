"""MLPerf's recommendation model: DLRM-DCNv2 on Criteo 1TB, multi-hot.

Sources: the MLPerf reference (mlcommons/training,
``recommendation_v2/torchrec_dlrm``; its inference counterpart is
mlcommons/inference ``recommendation/dlrm_v2``), DCN V2
(arXiv:2008.13535) and DLRM (arXiv:1906.00091).

13 dense features; 26 tables of 128-wide rows (204,184,588 rows in all),
each looked up by a multi-hot bag of a fixed published size (214 lookups
an item, at most 100 in one bag); bottom MLP 13-512-256-128; a three-layer
low-rank cross network of rank 512 over [bottom, 26 pooled bags], 3456
wide; over arch 3456-1024-1024-512-256-1, the last layer linear.
"""
from __future__ import annotations

from repro.configs.dlrm_paper import DLRMConfig, reduce_for_smoke

TABLE_ROWS = (40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63,
              40000000, 3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14,
              40000000, 40000000, 40000000, 590152, 12973, 108, 36)
MULTI_HOT_SIZES = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1,
                   12, 100, 27, 10, 3, 1, 1)

CONFIG = DLRMConfig(
    name="dlrm-dcnv2",
    num_dense_features=13,
    table_rows=TABLE_ROWS,
    embed_dim=128,
    avg_lookups_per_table=MULTI_HOT_SIZES,
    max_lookups_per_table=max(MULTI_HOT_SIZES),
    bottom_mlp=(512, 256, 128),
    top_mlp=(1024, 1024, 512, 256, 1),
    interaction="dcn",
    dcn_num_layers=3,
    dcn_low_rank_dim=512,
)

SMOKE = reduce_for_smoke(CONFIG)
