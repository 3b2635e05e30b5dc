"""Pure-jnp oracles for every Pallas kernel.  Tests sweep shapes/dtypes and
assert_allclose kernel(interpret=True) against these."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# retrieval scores at full float32 precision on every backend (a TPU's
# default f32 matmul is one bf16 pass), matching the Pallas scans
_HIGHEST = jax.lax.Precision.HIGHEST


def flash_attention_ref(q, k, v, *, causal: bool = False):
    """q: (B, Sq, H, D); k/v: (B, Sk, H, D) -> (B, Sq, H, D)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def vdb_topk_ref(queries, db, valid, k: int):
    """queries: (Q, D) L2-normalised; db: (N, D); valid: (N,) bool.
    Returns (scores (Q, k), idx (Q, k)) by cosine similarity."""
    scores = jnp.matmul(queries, db.T, precision=_HIGHEST)
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


def vdb_topk_sharded_ref(queries, slabs, valid, node_ids, k: int, *,
                         mask_nodes: bool = True):
    """queries: (Q, D); slabs: (n_idx, nodes, cap, D); valid: (nodes, cap);
    node_ids: (Q,).  Returns (scores, idx) of shape (n_idx, Q, k) with
    GLOBAL slot ids ``node * cap + col``; masked candidates are -inf.

    Shape-generic on the node axis, so the mesh-sharded scan
    (``vdb_topk_sharded_mesh``) reuses this oracle verbatim per device
    over its LOCAL node shard — shard-local node ids in, shard-local
    slot ids out, offset to global by the caller.  Ties (equal scores,
    and every -inf row) resolve to the LOWER flat index via
    ``jax.lax.top_k`` — the ordering contract the cross-shard merge
    reproduces."""
    n_idx, n_nodes, cap, _ = slabs.shape
    scores = jnp.einsum("qd,incd->iqnc", queries, slabs, precision=_HIGHEST)
    ok = valid[None, None, :, :]
    if mask_nodes:
        ok = ok & (node_ids[None, :, None, None]
                   == jnp.arange(n_nodes)[None, None, :, None])
    scores = jnp.where(ok, scores, -jnp.inf)
    flat = scores.reshape(n_idx, scores.shape[1], n_nodes * cap)
    return jax.lax.top_k(flat, k)


def vdb_topk_pernode_ref(queries, slabs, valid, k: int):
    """Per-node variant of the cluster scan: every query's top-k within
    EVERY node's slab (the schedule+retrieve fusion needs each node's own
    candidate set, not one global list a hot node could monopolise).

    queries: (Q, D); slabs: (n_idx, nodes, cap, D); valid: (nodes, cap).
    Returns (scores, idx) of shape (n_idx, nodes, Q, k) with GLOBAL slot
    ids ``node * cap + col``; masked candidates are -inf.  Shape-generic
    on the node axis (the mesh-sharded scan runs it per device on the
    local shard; per-node results need no cross-shard merge)."""
    n_idx, n_nodes, cap, _ = slabs.shape
    scores = jnp.einsum("qd,incd->inqc", queries, slabs, precision=_HIGHEST)
    scores = jnp.where(valid[None, :, None, :], scores, -jnp.inf)
    s, col = jax.lax.top_k(scores, k)
    gidx = col + (jnp.arange(n_nodes) * cap)[None, :, None, None]
    return s, gidx


def groupnorm_silu_ref(x, scale, bias, *, groups: int = 32, eps: float = 1e-5):
    """x: (B, H, W, C) -> silu(groupnorm(x))."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + eps)
    y = xg.reshape(b, h, w, c) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return (y * jax.nn.sigmoid(y)).astype(dtype)


def adaln_modulate_ref(x, shift, scale, *, eps: float = 1e-5):
    """Fused LN(affine-free) + adaLN modulation.
    x: (B, T, D); shift/scale: (B, D)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    xn = (x - mean) * jax.lax.rsqrt(var + eps)
    y = xn * (1.0 + scale.astype(jnp.float32)[:, None, :]) \
        + shift.astype(jnp.float32)[:, None, :]
    return y.astype(dtype)
