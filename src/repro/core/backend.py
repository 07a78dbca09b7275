"""Hardware backend specs for the analytic performance layer.

The roofline terms in ``launch/hlo_analysis.py`` and the serving perf
model (``serving/perf_model.py``) both need peak-rate constants.  They
come from one table keyed by the ``device_kind`` the chip reports
(``spec_for``), so a different part is a table entry, not a code edit,
and an unknown part is an error rather than a silent v5e.  The module
also places JAX's persistent compilation cache for the entry points
(``configure_compile_cache``).

Transfer-path asymmetry: the paper's deployment measured the
host->device ingest path sustaining ~0.868 words/cycle while the
device->host readback path (gather-contended) sustained only ~0.298
words/cycle — a ~2.9x asymmetry.  We carry that ratio on the spec so
snapshot/restore cost predictions charge the two directions
differently instead of assuming a symmetric link.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path
from typing import Dict

# Measured ingest/readback rates (words/cycle) from the accelerator
# bring-up; only the RATIO is used — absolute link bandwidth is a spec
# field in bytes/s.
H2D_WORDS_PER_CYCLE = 0.868
D2H_WORDS_PER_CYCLE = 0.298
D2H_H2D_RATIO = D2H_WORDS_PER_CYCLE / H2D_WORDS_PER_CYCLE   # ~0.343


@dataclass(frozen=True)
class BackendSpec:
    """Peak envelope of one accelerator chip.

    All rates are per-chip except ``ici_bw`` (per link).  ``h2d_bw`` is
    the host->device ingest bandwidth; ``d2h_bw`` the device->host
    readback bandwidth (typically much lower — gather contention).
    """
    name: str
    peak_flops_bf16: float        # FLOP/s, dense bf16/fp16
    peak_flops_int8: float        # FLOP/s, dense int8
    hbm_bw: float                 # bytes/s
    ici_bw: float                 # bytes/s per link
    h2d_bw: float                 # bytes/s, host -> device
    d2h_bw: float                 # bytes/s, device -> host

    def peak_flops(self, precision: str = "fp32") -> float:
        """Peak dense FLOP/s for an engine precision string.

        ``w8a8``/``int8`` run on the int8 path; everything else (fp32
        emulation included — the model is relative, the measured
        overhead factor absorbs the absolute scale) gets the bf16 peak.
        """
        if precision in ("w8a8", "int8"):
            return self.peak_flops_int8
        return self.peak_flops_bf16

    def precision_scale(self, precision: str = "fp32") -> float:
        """Predicted step-time multiplier vs the bf16/fp32 baseline
        (1.0 for fp32, 0.5 for w8a8 on a 2x-int8 part)."""
        return self.peak_flops_bf16 / self.peak_flops(precision)


# TPU v5e (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 394 TOP/s int8, 819 GB/s HBM), plus a PCIe-class host link with the
# readback asymmetry above applied.
TPU_V5E = BackendSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_flops_int8=394e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    h2d_bw=32e9,
    d2h_bw=32e9 * D2H_H2D_RATIO,
)

# Keyed by ``device.device_kind`` exactly as the chip reports it.
BACKENDS: Dict[str, BackendSpec] = {"TPU v5 lite": TPU_V5E}


def spec_for(device=None) -> BackendSpec:
    """The peak envelope of ``device`` (default: the first JAX device).

    An accelerator whose ``device_kind`` is not in ``BACKENDS`` raises —
    borrowing another part's peaks would misprice every knob the perf
    model sets. The CPU backend maps to v5e so tests and CPU rehearsals
    price the chip the program is written for."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return TPU_V5E
    try:
        return BACKENDS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no BackendSpec for device_kind {device.device_kind!r} "
            f"({device.platform}); known: {sorted(BACKENDS)}") from None


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself),
    else the fixed ``<checkout>/.jax_cache`` — a fixed path, because the
    path is part of what a later process must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    return str(Path(__file__).resolve().parents[3] / ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    for the program's entry points. Sets nothing when
    ``JAX_COMPILATION_CACHE_DIR`` is set. Returns the directory in use."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
