"""The harness finds cells, configurations, mixes and per-layer metrics by
name, as files: a checkout that holds only test-owned ones runs, at tiny
widths on the CPU. Off a chip the measurement path refuses to run."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run
from bench.tests.tiny import (DLRM_CFG, DLRM_MIX, DLRM_OPEN_MIX, LM_CFG,
                              LM_MIX, checkout)

BENCH = Path(run.__file__).resolve().parent


def result_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture
def root(tmp_path):
    return checkout(tmp_path, [("t-lm", LM_CFG, LM_MIX, 1),
                               ("t-dlrm", DLRM_CFG, DLRM_MIX, 1),
                               ("t-dlrm-open", DLRM_CFG, DLRM_OPEN_MIX, 1)])


@pytest.mark.parametrize("cell,trace,want", [
    ("t-lm", 0, {"ttft_p95_ms", "completed_rps", "setup_s"}),
    ("t-lm", 1, {"test.requests_due", "lm.queue_wait_p95_ms"}),
    ("t-dlrm", 0, {"rank_p95_ms", "completed_rps", "setup_s"}),
    ("t-dlrm", 1, {"test.requests_due", "dlrm.window_compiles"}),
    ("t-dlrm-open", 0, {"rank_p95_ms", "completed_rps", "setup_s"}),
])
def test_cell_runs_from_test_owned_files(root, jax_settings, capsys, cell,
                                         trace, want):
    code = run.main(["--workload", cell, "--seed", str(2 ** 33 + 7),
                     "--seconds", "2", "--trace", str(trace)], root=root,
                     platforms=("cpu",))
    assert code == 0
    res = result_line(capsys.readouterr().out)
    assert res["correct"] is True
    assert set(res["metrics"]) == want
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] >= 1
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"
    if trace:
        assert res["metrics"]["test.requests_due"]["value"] \
            == res["attempted"]
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_cpu_is_refused(root):
    with pytest.raises(run.Refused, match="platform 'cpu'"):
        run.main(["--workload", "t-lm", "--seed", "1", "--seconds", "1"],
                 root=root)


def test_a_directory_of_only_the_benchmark_has_no_result(tmp_path):
    """With BENCHMARK.json and the files under paths alone, the program is
    missing: the run exits non-zero and prints no result."""
    repo = BENCH.parent
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(
        (repo / "BENCHMARK.json").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "ds7b-chat", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "the program is missing" in p.stderr
    assert '"correct"' not in p.stdout


@pytest.fixture
def four_cpu_devices(tmp_path):
    """A child process with four virtual CPU devices: the flag must be set
    before JAX starts, so this worker's JAX cannot serve."""
    cfg = dict(LM_CFG, name="test-lm-x4", chips=4, replicas=4)
    root = checkout(tmp_path, [("t-lm-x4", cfg, LM_MIX, 4)])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import sys; sys.path[:0] = [sys.argv[1], sys.argv[1] + '/src']\n"
            "from bench import run\n"
            "sys.exit(run.main(['--workload', 't-lm-x4', '--seed', '5', "
            "'--seconds', '2'], root=sys.argv[2], platforms=('cpu',)))")
    return subprocess.run([sys.executable, "-c", code, str(BENCH.parent),
                           str(root)], env=env, capture_output=True,
                          text=True, timeout=300)


def test_four_pinned_replicas_on_four_cpu_devices(four_cpu_devices):
    p = four_cpu_devices
    assert p.returncode == 0, p.stderr[-3000:]
    res = result_line(p.stdout)
    assert res["correct"] is True
    assert res["device"]["count"] == 4
    assert "replica devices: 4 distinct" in p.stderr
