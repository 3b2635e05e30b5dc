"""Flash attention for TPU — online-softmax with VMEM-tiled BlockSpecs.

Grid layout: ``(batch, heads, q_blocks, k_blocks)`` with the k-block axis
innermost and sequential — the running max / sum / accumulator live in VMEM
scratch and persist across k iterations, exactly the memory-hierarchy-aware
structure flash attention needs on TPU:

  HBM  → (block_q × d) Q tile, (block_k × d) K/V tiles streamed per step
  VMEM → running m/l/acc scratch (block_q × d floats)
  MXU  → q·kᵀ and p·v contractions, 128-aligned tiles

Sequence padding and causality are handled by an in-kernel iota mask, so
arbitrary (non-multiple) lengths are correct.  Validated in interpret mode
against ``ref.flash_attention_ref`` over shape/dtype sweeps.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import CompilerParams, resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_k: int, kv_len: int, q_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (block_q, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (block_k, d)
    v = v_ref[0, 0].astype(jnp.float32)          # (block_k, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = cols < kv_len                         # padded-K validity
    if causal:
        # kv_len >= q_len aligns the END of q to the END of k (the
        # prefill/decode convention): row r attends keys ≤ r + (Sk − Sq)
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 0)
        mask = mask & (rows + (kv_len - q_len) >= cols)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                           # (block_q, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                        # (block_q, block_k)
    correction = jnp.exp(m_prev - m_new)          # (block_q, 1)
    l_new = correction * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * correction + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = False, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    """q: (B, Sq, H, D); k/v: (B, Sk, H, D) → (B, Sq, H, D).

    Sequence lengths are padded to the block size internally; D should be a
    multiple of 128 on real TPUs (MXU alignment) but any D works in
    interpret mode.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, max(sq, 1))
    block_k = min(block_k, max(sk, 1))
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k

    qt = jnp.moveaxis(q, 2, 1)                    # (B, H, Sq, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k
    n_q, n_k = sq_p // block_q, sk_p // block_k

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(d), causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k, kv_len=sk, q_len=sq)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    out = out[:, :, :sq]
    return jnp.moveaxis(out, 1, 2)
