"""chip_smoke.py's phases at toy widths on the CPU, its refusal to report
success off a TPU, and where the compile cache goes."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core import backend

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


def test_lm_phase_and_reference_check(smoke, capsys):
    sz = smoke.tiny_sizes()
    params = smoke.init_lm(sz.lm)
    reqs = smoke.lm_phase(sz, params)
    assert all(r.done and len(r.output) == sz.new_tokens for r in reqs)
    assert smoke.lm_reference_check(sz, params, reqs) <= smoke.GAP_TOL
    out = capsys.readouterr().out
    assert f"served {sz.requests} requests" in out
    assert "worst logit gap" in out


def test_reference_check_catches_a_wrong_token(smoke):
    sz = smoke.tiny_sizes()
    params = smoke.init_lm(sz.lm)
    reqs = smoke.lm_phase(sz, params)
    victim = min(reqs, key=lambda r: len(r.tokens))
    # the reference's least likely token is far outside the tolerance
    victim.output[1] = (victim.output[1] + sz.lm.vocab_size // 2) \
        % sz.lm.vocab_size
    with pytest.raises(SystemExit, match="FAIL"):
        smoke.lm_reference_check(sz, params, reqs)


def test_dlrm_phase_matches_reference(smoke, capsys):
    smoke.dlrm_phase(smoke.tiny_sizes())
    assert "worst error vs float64 reference" in capsys.readouterr().out


def test_never_reports_ok_off_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "jax " in out and "compile cache" in out


def test_four_replicas_pinned_to_four_devices():
    """The --four-chips phase on four virtual CPU devices: the device
    count binds when JAX starts, so this one runs in a child."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import chip_smoke as c; c.four_chip_phase(c.tiny_sizes()); "
            "import json, jax; print(json.dumps(sorted(str(d) for d in "
            "jax.devices())))")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.splitlines()
    assert any("token-identical to one replica" in l for l in lines)
    for i, dev in enumerate(json.loads(lines[-1])):
        assert any(l.startswith(f"replica {i}: device {dev}")
                   for l in lines), (i, dev)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_placement(monkeypatch, env_dir):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_cache")
        assert backend.configure_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]
    else:
        # JAX reads the variable itself: the program sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert backend.configure_compile_cache() == env_dir
        assert calls == []
    assert backend.compile_cache_dir() == \
        (env_dir or str(ROOT / ".jax_cache"))
