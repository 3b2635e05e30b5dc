"""Fused GroupNorm + SiLU — the UNet's ubiquitous pre-conv activation.

One VMEM round-trip instead of three (norm stats, affine, silu): the block
is a full (H·W, C) feature map per batch element, group statistics are
computed in-register, and the normalise+affine+silu epilogue is fused.
The whole map must fit VMEM: at float32 that holds up to about
64×64×256 (4 MiB); larger maps, such as a 256 px VAE's top stage
(256×256×128), need a tiled two-pass variant that does not exist yet.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import CompilerParams, resolve_interpret


# the whole (H*W, C) map of one image is one block: double-buffered in
# and out plus float32 temporaries need ~8x its bytes of VMEM (v5e: 128 MiB)
_VMEM_LIMIT = 96 * 2 ** 20


def _gn_kernel(x_ref, scale_ref, bias_ref, o_ref, *, groups: int,
               eps: float):
    x = x_ref[0].astype(jnp.float32)               # (H*W, C)
    hw, c = x.shape
    cg = c // groups
    # channel -> group membership (C, G); group statistics are two small
    # matmuls instead of a (H*W, G, C/G) reshape, which Mosaic rejects
    ch = jax.lax.broadcasted_iota(jnp.int32, (c, groups), 0)
    gi = jax.lax.broadcasted_iota(jnp.int32, (c, groups), 1)
    member = ((ch >= gi * cg) & (ch < (gi + 1) * cg)).astype(jnp.float32)

    def per_channel(row):                          # (1, C) -> group mean
        g = jax.lax.dot_general(row, member, (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        return jax.lax.dot_general(g, member, (((1,), (1,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32) \
            / (hw * cg)

    mean = per_channel(jnp.sum(x, axis=0, keepdims=True))
    xc = x - mean
    var = per_channel(jnp.sum(xc * xc, axis=0, keepdims=True))
    y = xc * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32) \
        + bias_ref[...].astype(jnp.float32)
    o_ref[0] = (y * jax.nn.sigmoid(y)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def groupnorm_silu(x, scale, bias, *, groups: int = 32, eps: float = 1e-5,
                   interpret: Optional[bool] = None):
    """x: (B, H, W, C); scale/bias: (C,) → silu(groupnorm(x))."""
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    kernel = functools.partial(_gn_kernel, groups=g, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h * w, c), lambda bi: (bi, 0, 0)),
            pl.BlockSpec((1, c), lambda bi: (0, 0)),
            pl.BlockSpec((1, c), lambda bi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h * w, c), lambda bi: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h * w, c), x.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(x.reshape(b, h * w, c), scale.reshape(1, c), bias.reshape(1, c))
    return out.reshape(b, h, w, c)
