"""A tiny DLRM-DCNv2 cell runs end to end on the CPU from test-owned files,
reports its end-to-end and per-layer metrics, and comes out not correct
when the timed path's cross network is broken. The FLOP count at the
published widths is the hand count."""
import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import counts_dcn, run

BENCH = Path(run.__file__).resolve().parent

DCN_CFG = {
    "kind": "dlrm_dcn", "name": "test-dcn", "source": "test-owned tiny DCN",
    "num_dense_features": 13, "embedding_dim": 16,
    "table_rows": [100 + 10 * i for i in range(6)],
    "avg_lookups_per_table": [1 + i % 4 for i in range(6)],
    "max_lookups_per_table": 8, "bottom_mlp": [32, 16],
    "interaction": "dcn", "dcn_num_layers": 3, "dcn_low_rank_dim": 8,
    "top_mlp": [64, 32, 1], "table_block_rows": 64,
    "engine": {"partial_transfers": True, "step_group": 4,
               "matmul_precision": "highest"},
    "chips": 1, "check": {"requests": 3, "error_limit": 3e-6}}
DCN_MIX = {"loop": "closed", "callers": 4, "items_per_request": 8,
           "bags": {"dist": "poisson_plus_one"},
           "ids": {"dist": "zipf", "alpha": 1.1}}
PER_LAYER = ("dlrm.dense_step_mfu", "dlrm.ingest_ms", "dlrm.sparse_roofline",
             "dlrm.window_compiles")


@pytest.fixture
def root(tmp_path):
    """A checkout whose only cell is the tiny DCN one, with the metrics the
    benchmark gives ``dlrm-rank``."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "configs" / "test-dcn.json").write_text(
        json.dumps(DCN_CFG))
    (tmp_path / "bench" / "traffic" / "t-rank.json").write_text(
        json.dumps(DCN_MIX))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    per_layer = [dict(m, workloads=["t-dcn"]) for m in real["per_layer"]
                 if m["name"] in PER_LAYER]
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "test-dcn", "source": "test",
                     "file": "bench/configs/test-dcn.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "t-dcn", "config": "test-dcn",
                       "traffic": "t-rank", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "completed_rps", "unit": "req/s", "better": "higher",
             "bound": 0.25, "source": "host_clock", "workloads": ["t-dcn"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": per_layer}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def result(capsys, root, trace: int, seed: int = 2 ** 33 + 11) -> dict:
    code = run.main(["--workload", "t-dcn", "--seed", str(seed),
                     "--seconds", "2", "--trace", str(trace)], root=root,
                    platforms=("cpu",))
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace,want", [
    (0, {"completed_rps", "setup_s"}),
    (1, set(PER_LAYER)),
])
def test_dcn_cell_runs_from_test_owned_files(root, jax_settings, capsys,
                                             trace, want):
    res = result(capsys, root, trace)
    assert res["correct"] is True
    assert set(res["metrics"]) == want
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:
        m = {k: v["value"] for k, v in res["metrics"].items()}
        assert m["dlrm.window_compiles"] == 0
        assert m["dlrm.ingest_ms"] > 0
        assert 0 < m["dlrm.dense_step_mfu"] and 0 < m["dlrm.sparse_roofline"]


def no_x0(mp):
    from repro.models import dlrm
    mp.setattr(dlrm, "cross_forward", lambda layers, x0: _cross(
        layers, x0, lambda x0, y: y))


def no_bias(mp):
    from repro.models import dlrm
    mp.setattr(dlrm, "cross_forward", lambda layers, x0: _cross(
        [dict(l, b=0 * l["b"]) for l in layers], x0, jnp.multiply))


def _cross(layers, x0, gate):
    x = x0
    for l in layers:
        x = gate(x0, (x @ l["v"]) @ l["w"] + l["b"]) + x
    return x


@pytest.mark.parametrize("fault", [no_x0, no_bias])
def test_a_broken_cross_network_is_not_correct(root, jax_settings, capsys,
                                               monkeypatch, fault):
    fault(monkeypatch)
    res = result(capsys, root, 0, seed=3)
    assert res["correct"] is False
    c = res["checks"]["score_error"]
    assert c["value"] > c["limit"]


def test_limits_calls_give_program_and_control_errors(root, jax_settings):
    """``bench/tools/limits.py``'s calls on the DCN cell: the control
    (three bfloat16 passes) reads far above the program."""
    import time
    from bench.drivers import dlrm_dcn
    bench, cell, cfg, traffic = run.load_cell(root, "t-dcn")
    ctx = run.Ctx(root, cell, cfg, traffic, 7, 1.0, 1, time.time(), {})
    c = dlrm_dcn.Cell(ctx)
    c.build(7)
    c.warm()
    win = c.window(7, 1.0)
    c.free()
    chk = c.check(7, win, control=True)
    assert chk["requests"] == 3 and win["compiles"] == 0
    assert chk["error"] < cfg["check"]["error_limit"] < chk["control_error"]


def test_dcn_flops_at_published_widths():
    cfg = json.loads((BENCH / "configs" / "dlrm-dcnv2-half.json").read_text())
    assert counts_dcn.dense_flops_per_item(cfg) == 32_060_928
    from repro.configs.dlrm_dcnv2 import CONFIG
    assert CONFIG.flops_per_sample() == 32_060_928
