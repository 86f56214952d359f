"""Pallas TPU kernels for the serving hot spots, with their XLA references.

The platform is the only kernel switch: on a TPU the model calls the
compiled kernels; everywhere else it calls the XLA references
(``repro.models.attention`` and friends). A kernel called directly on
another backend — a CPU test — runs in the Pallas interpreter.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    """True when the default backend is a TPU: the model then takes the
    compiled Pallas kernels instead of the XLA references."""
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: Optional[bool]) -> bool:
    """A kernel's ``interpret`` flag: compiled on a TPU, the Pallas
    interpreter elsewhere, unless the caller says otherwise. It reads the
    backend itself, so a CPU test that steers the model onto the kernel
    path (by patching ``on_tpu``) still interprets them."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
