"""The benchmark's tests import the program from ``src/`` and the
benchmark as the ``bench`` package, whatever PYTHONPATH says."""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# settings a run makes for its own process; a test that drives a run puts
# them back, so later tests in the same worker see JAX as it was
RUN_SETTINGS = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_default_matmul_precision",
                "jax_enable_compilation_cache")


@pytest.fixture
def jax_settings():
    saved = {k: getattr(jax.config, k) for k in RUN_SETTINGS}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
