"""Shared interpret-mode default for the Pallas kernels.

``default_interpret()`` is the one place that decides whether a kernel
wrapper interprets: everywhere except a real TPU backend, where Mosaic
compiles the kernels.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Pallas kernels interpret everywhere but TPU (Mosaic)."""
    return jax.default_backend() != "tpu"
