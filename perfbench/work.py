"""Operations and bytes of the served work, from shapes alone.

The counts say what the work requires, whatever implements it: a matmul
or convolution of an ``(m, k) x (k, n)`` shape costs ``2 m k n``
operations, elementwise work is not counted, and padding or inactive
slots that a program may compute anyway are not part of the work.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a device not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(table)})")
    return table[device_kind]


def dit_forward_flops(dit: dict) -> float:
    """One eps prediction of one latent: patch embedding, timestep MLP,
    conditioning projection, ``depth`` blocks (adaLN modulation, qkv,
    attention scores and values, output projection, MLP), final
    modulation and projection."""
    d = dit["hidden_size"]
    p, c = dit["patch_size"], dit["in_channels"]
    n = (dit["input_size"] // p) ** 2
    h = int(d * dit["mlp_ratio"])
    patch = p * p * c
    per_image = (256 * d + d * d          # timestep MLP
                 + dit["cond_dim"] * d)   # conditioning projection
    per_token = patch * d                 # patch embedding
    block_image = 6 * d * d               # adaLN modulation
    block_token = 4 * d * d + 2 * d * h + 2 * n * d
    macs = (per_image + n * per_token
            + dit["depth"] * (block_image + n * block_token)
            + 2 * d * d + n * d * patch)  # final modulation + projection
    return 2.0 * macs


def _conv_macs(h: int, w: int, k: int, cin: int, cout: int) -> int:
    return h * w * k * k * cin * cout


def vae_decode_flops(vae: dict, image_res: int) -> float:
    """Latent to image: 1x1 from_z, per stage a 3x3 conv to 4x channels
    and a pixel shuffle then ``n_res`` residual blocks, 3x3 to_img."""
    mults = list(vae["ch_mult"])
    r = image_res // 2 ** len(mults)
    ch = vae["base_ch"] * mults[-1]
    macs = _conv_macs(r, r, 1, vae["z_ch"], ch)
    for m in reversed(mults):
        out = vae["base_ch"] * m
        macs += _conv_macs(r, r, 3, ch, 4 * out)
        r *= 2
        macs += vae["n_res"] * 2 * _conv_macs(r, r, 3, out, out)
        ch = out
    macs += _conv_macs(r, r, 3, ch, vae["in_ch"])
    return 2.0 * macs


def vae_encode_flops(vae: dict, image_res: int) -> float:
    """Image to latent moments: 3x3 stem, per stage a stride-2 3x3 conv
    then ``n_res`` residual blocks, 1x1 to the moments."""
    r = image_res
    ch = vae["base_ch"]
    macs = _conv_macs(r, r, 3, vae["in_ch"], ch)
    for m in vae["ch_mult"]:
        out = vae["base_ch"] * m
        r //= 2
        macs += _conv_macs(r, r, 3, ch, out)
        macs += vae["n_res"] * 2 * _conv_macs(r, r, 3, out, out)
        ch = out
    macs += _conv_macs(r, r, 1, ch, 2 * vae["z_ch"])
    return 2.0 * macs


def scan_work(queries: int, rows: int, dim: int,
              planes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one exact scan of ``queries`` against the
    ``rows`` valid float32 rows of each of ``planes`` index planes."""
    flops = 2.0 * queries * rows * dim * planes
    nbytes = 4.0 * (rows * dim * planes + queries * dim)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["peak_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def window_flops(cfg: dict, rec, window, rows: int) -> float:
    """Operations the window's completed work requires: a DiT forward
    per active slot-step, a VAE decode per generated image, a VAE encode
    per img2img start, and every scan over ``rows`` valid rows."""
    res = cfg["image_res"]
    return (dit_forward_flops(cfg["dit"]) * sum(window.slot_occupancy)
            + vae_decode_flops(cfg["vae"], res)
            * len(rec.walls.get("slot_decode", ()))
            + vae_encode_flops(cfg["vae"], res) * rec.img_inits
            + sum(scan_work(q, rows, cfg["fleet"]["dim"])[0]
                  for q in rec.scan_queries))
