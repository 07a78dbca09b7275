"""Least FLOPs and bytes against hand counts at tiny shapes."""
import pytest

from bench import counts

DM = counts.Dims(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16,
                 vocab=32)
DLRM = {"embedding_dim": 4, "table_rows": [10, 20], "num_dense_features": 3,
        "bottom_mlp": [5, 4], "top_mlp": [6, 1]}


def test_layer_params():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16
    assert DM.layer_matmul_params == 64 + 32 + 32 + 64 + 384 == 576


@pytest.mark.parametrize("n,want", [
    # 2 layers x (2*576*n + 4*2*4 * n(n+1)/2) + head 2*8*32 at one position
    (1, 2 * (1152 + 32) + 512),
    (3, 2 * (3456 + 32 * 6) + 512),
])
def test_prefill_flops(n, want):
    assert counts.prefill_flops(DM, n) == want


def test_decode_flops():
    assert counts.decode_flops(DM, 5) == 2 * (1152 + 32 * 5) + 512


def test_decode_step_bytes():
    # weights (2*576 + 32*8) * 2 B; K and V of 2 layers, 1 head of 4: 32 B
    assert counts.kv_bytes_per_position(DM) == 32
    assert counts.decode_step_bytes(DM, [3, 4]) == (1152 + 256) * 2 + 7 * 32


def test_flash_attn_least():
    # pairs 3 + 6; per pair 4*2*4 flops; Q+O 2x8 and K+V 2x4 values per token
    assert counts.flash_attn_least(DM, [2, 3]) == (32 * 9, 5 * 24 * 2)


@pytest.mark.parametrize("flops,nbytes,want", [
    (288, 240, (24.0, "memory")),
    (3000, 240, (30.0, "compute")),
])
def test_roofline_time(flops, nbytes, want):
    assert counts.roofline_time(flops, nbytes, 100, 10) == want


def test_dlrm_counts():
    # bottom 3x5 + 5x4, interaction 3x3 pairs of dim 4, top (4+3)x6 + 6x1
    assert counts.dlrm_flops_per_sample(DLRM) == 2 * 35 + 72 + 2 * 48
    assert counts.sparse_least_bytes(DLRM, 10, 2) == 10 * 8 + 2 * 2 * 4 * 4


def test_dlrm_flops_agree_with_the_program_config():
    from repro.configs import dlrm_paper
    c = dlrm_paper.PAPER_COMPLEX
    cfg = {"embedding_dim": c.embed_dim, "table_rows": list(c.table_rows),
           "num_dense_features": c.num_dense_features,
           "bottom_mlp": list(c.bottom_mlp), "top_mlp": list(c.top_mlp)}
    assert counts.dlrm_flops_per_sample(cfg) == c.flops_per_sample()


def test_lm_params_agree_with_the_program_config():
    from bench.drivers.lm import program_config
    from bench.workload import load
    from pathlib import Path
    cfg = load(Path(__file__).parents[1] / "configs" / "deepseek-7b-16l.json")
    pc = program_config(cfg)
    dm = counts.Dims.of(cfg)
    norms = 2 * pc.d_model
    assert dm.layer_matmul_params == pc._block_params("global") - norms
