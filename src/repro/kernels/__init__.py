"""Pallas kernels for the paper's hot spots (SLS, w8a8 FC, attention).

Every kernel entry point takes ``interpret=None`` and resolves it here:
the Pallas interpreter runs only on the CPU backend (tests), so a call
on the chip always runs the compiled kernel.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The caller's explicit choice, else True only on the CPU backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
