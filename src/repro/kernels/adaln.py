"""Fused affine-free LayerNorm + adaLN modulation (DiT block prologue).

y = LN(x) * (1 + scale[b]) + shift[b], fused into one VMEM pass: the DiT
calls this twice per block, and unfused it costs three HBM round-trips of
the (B, T, D) activation.  Token-tiled BlockSpec: (1, block_t, D) per grid
step, per-row statistics in-register.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import CompilerParams, resolve_interpret


def _adaln_kernel(x_ref, shift_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[0].astype(jnp.float32)               # (block_t, D)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    xn = (x - mean) * jax.lax.rsqrt(var + eps)
    sh = shift_ref[0].astype(jnp.float32)          # (1, D) row for batch b
    sc = scale_ref[0].astype(jnp.float32)
    o_ref[0] = (xn * (1.0 + sc) + sh).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "eps", "interpret"))
def adaln_modulate(x, shift, scale, *, block_t: int = 256, eps: float = 1e-5,
                   interpret: Optional[bool] = None):
    """x: (B, T, D); shift/scale: (B, D) → (B, T, D)."""
    b, t, d = x.shape
    block_t = min(block_t, t)
    pad_t = (-t) % block_t
    if pad_t:
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, 0)))
    n_t = (t + pad_t) // block_t
    kernel = functools.partial(_adaln_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=(b, n_t),
        in_specs=[
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, 1, d), lambda bi, ti: (bi, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda bi, ti: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t + pad_t, d), x.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(x, shift.reshape(b, 1, d), scale.reshape(b, 1, d))
    return out[:, :t]
