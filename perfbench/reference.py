"""Plain reference of what the served path computes.

Written from the published descriptions, in straightforward ``jax.numpy``
and float32 at HIGHEST matmul precision (numpy float64 for retrieval).
It imports nothing of the program: it reads the weights the benchmark
made, by the names of their dictionary keys, and the inputs the timed
path was given.

* DiT (Peebles & Xie, arXiv:2212.09748): patchify, linear patch
  embedding plus a learned position table, sinusoidal timestep embedding
  through a two-layer GELU MLP plus a linear projection of the pooled
  conditioning vector, ``depth`` adaLN-Zero blocks (LayerNorm without
  affine, shift/scale/gate from SiLU(cond)), multi-head self-attention
  and a 4x GELU MLP, then a modulated final LayerNorm and linear layer,
  unpatchified to the latent's shape.  Output: eps.
* DDIM (Song et al., eta 0) over the configuration's linear beta
  schedule, x0 clipped to [-4, 4]; ``t_prev < 0`` marks a chain's last
  update.
* The f8 VAE: 3x3 SAME convolutions, GroupNorm(32)+SiLU residual blocks,
  stride-2 down convolutions in the encoder and convolution then 2x2
  pixel shuffle in the decoder.
* Retrieval: float64 cosine top-k per node over both index planes, with
  the union of the two planes deduplicated by slot.

``prec`` selects how every matmul and convolution treats its operands:
``"f32"`` is the reference; ``"fp8"`` rounds both operands to float8
e4m3 with one scale per tensor first: the control, one step below the
bfloat16 operands that the served path's default precision gives.  The
scans' control is three bfloat16 passes, one step below HIGHEST.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _q(x, prec: str):
    if prec == "f32":
        return x
    if prec != "fp8":
        raise ValueError(prec)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, prec):
    return jnp.matmul(_q(x, prec), _q(w, prec), precision=HI)


def _dense(p, x, prec):
    y = _mm(x, p["w"], prec)
    return y + p["b"] if "b" in p else y


def _layernorm(x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _gelu(x):   # tanh form, as DiT's MLPs use
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _mlp(p, x, prec):
    return _dense(p["fc2"], _gelu(_dense(p["fc1"], x, prec)), prec)


def timestep_embedding(t, dim=256, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half) / half)
    a = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(a), jnp.sin(a)], -1)


@functools.partial(jax.jit, static_argnames=("patch", "prec"))
def _dit_embed(params, x, t, ctx, patch: int, prec: str):
    b, h, w, c = x.shape
    p = patch
    tok = x.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    tok = tok.reshape(b, (h // p) * (w // p), p * p * c)
    z = _dense(params["patch_embed"], tok, prec) + params["pos_embed"][None]
    cond = (_mlp(params["t_mlp"], timestep_embedding(t), prec)
            + _dense(params["ctx_proj"], ctx, prec))
    return z, _silu(cond)


@functools.partial(jax.jit, static_argnames=("heads", "prec"))
def _dit_block(blocks, i, z, sc, heads: int, prec: str):
    bp = jax.tree_util.tree_map(lambda a: a[i], blocks)
    b, n, d = z.shape
    m = _dense(bp["ada"], sc, prec)[:, None, :]
    sh1, s1, g1, sh2, s2, g2 = jnp.split(m, 6, axis=-1)
    hh = _layernorm(z) * (1 + s1) + sh1
    qkv = _dense(bp["qkv"], hh, prec).reshape(b, n, 3, heads, d // heads)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    logits = jnp.einsum("bqhd,bkhd->bhqk", _q(q, prec), _q(k, prec),
                        precision=HI) / math.sqrt(d // heads)
    att = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _q(att, prec), _q(v, prec),
                   precision=HI).reshape(b, n, d)
    z = z + g1 * _dense(bp["proj"], o, prec)
    hh = _layernorm(z) * (1 + s2) + sh2
    return z + g2 * _mlp(bp["mlp"], hh, prec)


@functools.partial(jax.jit, static_argnames=("shape", "patch", "prec"))
def _dit_final(params, z, sc, shape, patch: int, prec: str):
    b, h, w, c = shape
    p = patch
    m = _dense(params["final_ada"], sc, prec)[:, None, :]
    shift, scale = jnp.split(m, 2, axis=-1)
    z = _dense(params["final_proj"], _layernorm(z) * (1 + scale) + shift,
               prec)
    z = z.reshape(b, h // p, w // p, p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return z.reshape(b, h, w, c)


def dit_eps(params, cfg: dict, x, t, ctx, prec: str = "f32"):
    """eps of latents ``x`` (B, H, W, C) at timesteps ``t`` (B,) under
    pooled conditioning ``ctx`` (B, cond_dim); ``cfg`` is the
    configuration file's ``dit`` group.  One compiled program per piece
    (embedding, block, output), the blocks run one by one."""
    p = cfg["patch_size"]
    z, sc = _dit_embed(params, x, t, ctx, patch=p, prec=prec)
    for i in range(cfg["depth"]):
        z = _dit_block(params["blocks"], i, z, sc, heads=cfg["num_heads"],
                       prec=prec)
    return _dit_final(params, z, sc, shape=tuple(x.shape), patch=p,
                      prec=prec)


def alphas_bar(sampler: dict) -> np.ndarray:
    """Cumulative alpha-bar of the linear beta schedule, in float64."""
    betas = np.linspace(sampler["beta_start"], sampler["beta_end"],
                        sampler["T"], dtype=np.float64)
    return np.cumprod(1.0 - betas)


@jax.jit
def _ddim_step(x, eps, t, t_prev, ab):
    a_t = ab[t][:, None, None, None]
    a_p = jnp.where(t_prev >= 0, ab[jnp.maximum(t_prev, 0)], 1.0)
    a_p = a_p[:, None, None, None]
    x0 = jnp.clip((x - jnp.sqrt(1 - a_t) * eps) / jnp.sqrt(a_t), -4.0, 4.0)
    return jnp.sqrt(a_p) * x0 + jnp.sqrt(jnp.maximum(1 - a_p, 0.0)) * eps


def ddim_step(sampler: dict, x, eps, t, t_prev):
    """One deterministic DDIM update per slot; ``t``/``t_prev`` (B,)."""
    return _ddim_step(x, eps, t, t_prev,
                      jnp.asarray(alphas_bar(sampler), jnp.float32))


def ddim_timesteps(steps: int, t_start: int):
    """The strided descending DDIM sub-sequence from ``t_start - 1``."""
    return np.linspace(0, t_start - 1, steps).round().astype(np.int64)[::-1]


def _conv(p, x, prec, stride=1):
    y = jax.lax.conv_general_dilated(
        _q(x, prec), _q(p["w"], prec), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    return y + p["b"]


def _groupnorm(p, x, groups=32, eps=1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mu = xg.mean((1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean((1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def _resblock(p, x, prec):
    h = _conv(p["conv1"], _silu(_groupnorm(p["norm1"], x)), prec)
    h = _conv(p["conv2"], _silu(_groupnorm(p["norm2"], h)), prec)
    return h + (_conv(p["skip"], x, prec) if "skip" in p else x)


@functools.partial(jax.jit, static_argnames=("n_stages", "n_res", "prec"))
def _vae_decode(params, z, n_stages: int, n_res: int, prec: str):
    d = params["dec"]
    h = _conv(d["from_z"], z, prec)
    for si in range(n_stages):
        st = d[f"stage{si}"]
        h = _conv(st["up"], h, prec)
        b, hh, ww, c4 = h.shape
        h = h.reshape(b, hh, ww, 2, 2, c4 // 4).transpose(0, 1, 3, 2, 4, 5)
        h = h.reshape(b, hh * 2, ww * 2, c4 // 4)
        for ri in range(n_res):
            h = _resblock(st[f"res{ri}"], h, prec)
    return _conv(d["to_img"], _silu(_groupnorm(d["norm_out"], h)), prec)


def vae_decode(params, cfg: dict, z, prec: str = "f32"):
    """Latents (B, h, w, 4) to images (B, 8h, 8w, 3); ``cfg`` is the
    configuration file's ``vae`` group."""
    return _vae_decode(params, z, n_stages=len(cfg["ch_mult"]),
                       n_res=cfg["n_res"], prec=prec)


def vae_encode_mean(params, cfg: dict, img, prec: str = "f32"):
    """Images (B, H, W, 3) to the latent mean (B, H/8, W/8, 4)."""
    e = params["enc"]
    h = _conv(e["stem"], img, prec)
    for si in range(len(cfg["ch_mult"])):
        st = e[f"stage{si}"]
        h = _conv(st["down"], h, prec, stride=2)
        for ri in range(cfg["n_res"]):
            h = _resblock(st[f"res{ri}"], h, prec)
    m = _conv(e["to_moments"], _silu(_groupnorm(e["norm_out"], h)), prec)
    return m[..., : m.shape[-1] // 2]


# ---------------------------------------------------------------- retrieval


def _bf16_3x(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """float32 ``a @ b.T`` as three bfloat16 passes (hi*hi + hi*lo +
    lo*hi): the precision one step below HIGHEST on a TPU."""
    bf = ml_dtypes.bfloat16

    def split(x):
        hi = x.astype(bf).astype(np.float32)
        return hi, (x - hi).astype(bf).astype(np.float32)

    ah, al = split(a)
    bh, bl = split(b)
    return ah @ bh.T + ah @ bl.T + al @ bh.T


def union_topk(scores, slots):
    """Union of per-plane candidates, best score per slot, descending
    with ascending-slot ties (the served scan's result order)."""
    if not scores:
        return np.zeros(0), np.zeros(0, np.int64)
    s = np.concatenate([np.ravel(x) for x in scores])
    i = np.concatenate([np.ravel(x) for x in slots]).astype(np.int64)
    if s.size == 0:
        return s, i
    order = np.lexsort((-s, i))
    i, s = i[order], s[order]
    first = np.ones(len(i), bool)
    first[1:] = i[1:] != i[:-1]
    i, s = i[first], s[first]
    out = np.lexsort((i, -s))
    return s[out], i[out]


def topk_nodes(queries: np.ndarray, img_planes, txt_planes, valids,
               k: int, margin: int = 16):
    """Per (query, node): the union of each index plane's top ``k`` rows
    among the node's valid rows, scored in float64.  Candidates come
    from one float32 pass (``k + margin`` per plane, far wider than
    float32's error can reorder) and are rescored in float64.  Returns
    ``out[query][node] = (scores, slots)``, and the same top-k scored and
    ranked by three bfloat16 passes: the control, in the served scan's
    form."""
    q64 = np.asarray(queries, np.float64)
    q64 = q64 / np.maximum(np.linalg.norm(q64, axis=-1, keepdims=True),
                           1e-12)
    q32 = q64.astype(np.float32)
    out = [[None] * len(valids) for _ in range(len(q64))]
    low = [[None] * len(valids) for _ in range(len(q64))]
    for node, (img, txt, valid) in enumerate(zip(img_planes, txt_planes,
                                                 valids)):
        per_q = [([], [], [], []) for _ in range(len(q64))]
        n_valid = int(valid.sum())
        for plane in (img, txt):
            s32 = plane @ q32.T                              # (rows, Q)
            s32[~valid] = -np.inf
            kk = min(k + margin, n_valid)
            if kk == 0:
                continue
            for qi in range(len(q64)):
                cand = np.argpartition(-s32[:, qi], kk - 1)[:kk]
                rows = plane[cand]
                s = rows.astype(np.float64) @ q64[qi]
                top = np.lexsort((cand, -s))[:k]
                per_q[qi][0].append(s[top])
                per_q[qi][1].append(cand[top])
                s3 = _bf16_3x(rows, q32[qi][None])[:, 0]
                top3 = np.lexsort((cand, -s3))[:k]
                per_q[qi][2].append(s3[top3])
                per_q[qi][3].append(cand[top3])
        for qi, (ss, ii, ss3, ii3) in enumerate(per_q):
            out[qi][node] = union_topk(ss, ii)
            low[qi][node] = union_topk(ss3, ii3)
    return out, low
