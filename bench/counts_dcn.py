"""Least operations of DLRM-DCNv2's dense side, from the configuration's
shapes (the numerator of ``dlrm.dense_step_mfu``): 2 flops per
multiply-add of the bottom MLP, of ``x @ V`` and ``(x V) @ W`` in every
cross layer, and of the top MLP. Elementwise work is not counted."""
from __future__ import annotations


def mlp_flops(dims) -> int:
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def dense_flops_per_item(cfg: dict) -> int:
    d = (len(cfg["table_rows"]) + 1) * cfg["embedding_dim"]
    cross = cfg["dcn_num_layers"] * 2 * 2 * d * cfg["dcn_low_rank_dim"]
    return (mlp_flops([cfg["num_dense_features"], *cfg["bottom_mlp"]])
            + cross + mlp_flops([d, *cfg["top_mlp"]]))
