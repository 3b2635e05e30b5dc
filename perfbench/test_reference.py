"""The plain reference agrees with the program at HIGHEST precision, on the
CPU at a reduced size: what the chip comparison relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import reference as ref
import tiny

# float32 through two different op orders: a few ulp per operation,
# summed over a few dozen operations
TOL_F32 = 2e-5
# the program's alpha-bar is a float32 cumprod over 1000 betas, the
# reference's a float64 one: up to about 1e-4 relative at t near 999
TOL_SCHEDULE = 2e-4


@pytest.fixture(scope="module")
def cfg():
    return tiny.config()


@pytest.fixture(scope="module")
def weights(cfg):
    return bench.make_weights(cfg, 2 ** 40 + 3)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def test_dit_eps_matches_the_program(cfg, weights):
    from repro.models.diffusion import dit
    net_cfg, _ = bench.program_configs(cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 8, 8, 4)), jnp.float32)
    t = jnp.asarray([999, 500, 3], jnp.int32)
    ctx = jnp.asarray(rng.standard_normal((3, 512)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = dit.apply_dit(weights[0], net_cfg, x, t, ctx)
    got = ref.dit_eps(weights[0], cfg["dit"], x, t, ctx)
    assert float(jnp.max(jnp.abs(want))) > 0.1   # eps is not trivially 0
    assert _rel(got, want) < TOL_F32


def test_ddim_step_matches_the_program(cfg):
    from repro.models.diffusion.sampler import ddim_step_slots
    from repro.models.diffusion.schedule import DiffusionSchedule
    smp = cfg["sampler"]
    sched = DiffusionSchedule.linear(smp["T"], smp["beta_start"],
                                     smp["beta_end"])
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 8, 8, 4)), jnp.float32)
    eps = jnp.asarray(rng.standard_normal((4, 8, 8, 4)), jnp.float32)
    t = jnp.asarray([999, 600, 33, 0], jnp.int32)
    tp = jnp.asarray([965, 579, 0, -1], jnp.int32)
    want = ddim_step_slots(sched, x, eps, t, tp)
    got = ref.ddim_step(smp, x, eps, t, tp)
    assert _rel(got, want) < TOL_SCHEDULE


def test_vae_matches_the_program(cfg, weights):
    from repro.models.diffusion import vae
    _, vae_cfg = bench.program_configs(cfg)
    rng = np.random.default_rng(2)
    z = jnp.asarray(rng.standard_normal((2, 8, 8, 4)), jnp.float32)
    img = jnp.asarray(rng.uniform(-1, 1, (2, 32, 32, 3)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        dec = vae.decode(weights[1], vae_cfg, z)
        mean, _ = vae.encode(weights[1], vae_cfg, img)
    assert _rel(ref.vae_decode(weights[1], cfg["vae"], z), dec) < TOL_F32
    assert _rel(ref.vae_encode_mean(weights[1], cfg["vae"], img),
                mean) < TOL_F32


def test_fp8_control_departs_from_the_reference(cfg, weights):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 4)), jnp.float32)
    t = jnp.asarray([700, 50], jnp.int32)
    ctx = jnp.asarray(rng.standard_normal((2, 512)), jnp.float32)
    f32 = ref.dit_eps(weights[0], cfg["dit"], x, t, ctx)
    fp8 = ref.dit_eps(weights[0], cfg["dit"], x, t, ctx, prec="fp8")
    assert _rel(fp8, f32) > 100 * TOL_F32


def test_topk_matches_the_programs_scan():
    from repro.core.cluster_index import ClusterIndex
    from repro.core.vdb import VectorDB
    rng = np.random.default_rng(4)
    dim, cap, k = 64, 300, 8
    dbs = [VectorDB(dim, cap, name=f"n{i}") for i in range(3)]
    for i, db in enumerate(dbs):
        n = [300, 17, 0][i]
        if n:
            db.add(rng.standard_normal((n, dim)), rng.standard_normal(
                (n, dim)), np.arange(n) + 1000 * i, t=0.0)
    ci = ClusterIndex.from_dbs(dbs, use_pallas=False)
    q = rng.standard_normal((5, dim)).astype(np.float32)
    got = ci.search_cluster_nodes(q, k)
    want, control = ref.topk_nodes(
        q, [db.img_vecs for db in dbs], [db.txt_vecs for db in dbs],
        [db.valid for db in dbs], k)
    worst = 0.0
    for qi in range(5):
        for node in range(3):
            (gs, gi), (ws, wi) = got[qi][node], want[qi][node]
            assert list(gi) == list(wi)
            np.testing.assert_allclose(gs, ws, atol=1e-6)
            cs, _ = control[qi][node]
            assert len(cs) == len(ws)
            if len(cs):
                worst = max(worst, float(np.max(np.abs(cs - ws))))
    # three bfloat16 passes: about 1e-5 on unit vectors of 64 dims
    assert 1e-7 < worst < 1e-3
