"""Pallas TPU kernels, their jnp reference oracles (``ref.py``) and the
public entry points (``ops.py``).

Every kernel takes ``interpret: Optional[bool] = None``; ``None`` resolves
by backend through :func:`resolve_interpret`, so a kernel compiles through
Mosaic on a TPU and runs the same body in interpret mode elsewhere.
"""
from typing import Optional

import jax
from jax.experimental.pallas.tpu import CompilerParams

__all__ = ["CompilerParams", "resolve_interpret"]


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Backend-aware interpret default: only interpret when no TPU/Mosaic
    backend is available to compile the kernel."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
