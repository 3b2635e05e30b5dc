"""The operation counts, against XLA's own count of the program's
functions at the served widths (lowered on the CPU, nothing runs)."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import bench
import work

HERE = os.path.dirname(os.path.abspath(__file__))

# XLA also counts elementwise work (norms, softmax, activations) and
# leaves out the taps of a convolution that fall on padding, each a few
# tenths of a percent to about 2% of these totals
TOL = 0.03


def _cfg(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["dit_b2", "dit_l2"])
def test_dit_forward_flops_match_xla_per_block(name):
    from repro.models.diffusion import dit
    cfg = _cfg(name)
    one = dict(cfg["dit"], depth=1)   # XLA counts a scan's body once
    net_cfg, _ = bench.program_configs(dict(cfg, dit=one))
    params = jax.eval_shape(lambda k: dit.init_dit(k, net_cfg),
                            jax.random.key(0))
    x = jax.ShapeDtypeStruct((1, 32, 32, 4), jnp.float32)
    t = jax.ShapeDtypeStruct((1,), jnp.int32)
    c = jax.ShapeDtypeStruct((1, 512), jnp.float32)
    xla = jax.jit(lambda p, x, t, c: dit.apply_dit(p, net_cfg, x, t, c)
                  ).lower(params, x, t, c).cost_analysis()["flops"]
    assert work.dit_forward_flops(one) == pytest.approx(xla, rel=TOL)


def test_dit_forward_flops_at_published_sizes():
    # 12 d^2 + 2 N d multiply-adds per token per layer, N = 256 tokens
    assert work.dit_forward_flops(_cfg("dit_b2")["dit"]) == pytest.approx(
        2 * 12 * 256 * (12 * 768 ** 2 + 2 * 256 * 768), rel=0.01)
    assert work.dit_forward_flops(_cfg("dit_l2")["dit"]) == pytest.approx(
        2 * 24 * 256 * (12 * 1024 ** 2 + 2 * 256 * 1024), rel=0.01)


def test_vae_flops_match_xla():
    from repro.models.diffusion import vae
    cfg = _cfg("dit_b2")
    _, vae_cfg = bench.program_configs(cfg)
    params = jax.eval_shape(lambda k: vae.init_vae(k, vae_cfg),
                            jax.random.key(0))
    z = jax.ShapeDtypeStruct((1, 32, 32, 4), jnp.float32)
    img = jax.ShapeDtypeStruct((1, 256, 256, 3), jnp.float32)
    dec = jax.jit(lambda p, z: vae.decode(p, vae_cfg, z)).lower(
        params, z).cost_analysis()["flops"]
    enc = jax.jit(lambda p, x: vae.encode(p, vae_cfg, x)).lower(
        params, img).cost_analysis()["flops"]
    assert work.vae_decode_flops(cfg["vae"], 256) == pytest.approx(
        dec, rel=TOL)
    assert work.vae_encode_flops(cfg["vae"], 256) == pytest.approx(
        enc, rel=TOL)
    assert 325e9 < work.vae_decode_flops(cfg["vae"], 256) < 335e9


def test_scan_work_and_roofline():
    flops, nbytes = work.scan_work(8, 524288 - 16384, 512)
    assert nbytes == 4 * (2 * (524288 - 16384) * 512 + 8 * 512)
    assert flops == 2 * 8 * (524288 - 16384) * 512 * 2
    peak = work.peaks("TPU v5 lite")
    # memory-bound: about 2.5 ms for the 2 GB fleet at 819 GB/s
    assert work.roofline_seconds(flops, nbytes, peak) == pytest.approx(
        nbytes / 819e9)
    with pytest.raises(KeyError):
        work.peaks("cpu")
