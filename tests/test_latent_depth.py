"""Latent-depth reference caching (PR 6) + the accounting/eviction bugfix
sweep that rides along.

Tentpole coverage: the depth schedule's band boundaries, depth metadata on
the VDB slabs (fused-scan parity included), per-depth eviction utility
under one C_max, the k=0 resume parity invariant on both backends, and the
end-to-end strictly-fewer-steps win on the band-mutation workload.

Bugfix sweep coverage: scheduler strict schedule/complete pairing (no
silent clamp), fresh-entry access_count=1 under LFU, CostModel rate
validation for non-default fleets, and the resumed-path Eq. 8 latency
accounting (t_latent replaces t_noise).
"""
from __future__ import annotations

import warnings

import numpy as np
import pytest

from repro.core.cluster_index import ClusterIndex
from repro.core.latency_model import CostModel, LatencyModel
from repro.core.lcu import LCUPolicy, LFUPolicy
from repro.core.policy import GenerationPolicy, Route
from repro.core.scheduler import NodeInfo, RequestScheduler
from repro.core.trace import band_mutation_trace
from repro.core.vdb import VectorDB
from repro.launch.serve import NullBackend, build_system


# ---------------------------------------------------------------------------
# depth schedule (policy layer)
# ---------------------------------------------------------------------------


def test_default_latent_depths_quartiles():
    pol = GenerationPolicy(steps_ref=20)
    assert pol.default_latent_depths() == (5, 10, 15)
    # tiny chains: quartiles that collapse to 0 are dropped, dupes merged
    assert GenerationPolicy(steps_ref=2).default_latent_depths() == (1,)
    assert GenerationPolicy(steps_ref=4).default_latent_depths() == (1, 2, 3)


def test_resume_depth_band_boundaries():
    """[lo, hi] splits into len(depths)+1 equal sub-bands over the levels
    (0,) + latent_depths; an exact sub-band edge belongs to the DEEPER
    side, and scores outside the band clamp to the extremes.  Edge
    semantics are pinned on a [0, 1] band where the sub-band boundaries
    (0.25, 0.5, 0.75) are exactly representable floats."""
    unit = GenerationPolicy(lo=0.0, hi=1.0, steps_ref=20,
                            latent_depths=(5, 10, 15))
    assert unit.resume_depth(0.0) == 0       # band floor
    assert unit.resume_depth(0.249) == 0     # just inside first sub-band
    assert unit.resume_depth(0.25) == 5      # exact edge -> deeper side
    assert unit.resume_depth(0.5) == 10
    assert unit.resume_depth(0.75) == 15
    assert unit.resume_depth(1.0) == 15      # band ceiling
    # paper-default band: clamping + interior sub-band membership
    pol = GenerationPolicy(lo=0.4, hi=0.5, steps_ref=20,
                           latent_depths=(5, 10, 15))
    assert pol.resume_depth(0.30) == 0       # below band: shallowest
    assert pol.resume_depth(0.41) == 0
    assert pol.resume_depth(0.46) == 10
    assert pol.resume_depth(0.49) == 15
    assert pol.resume_depth(0.90) == 15      # above band: deepest
    # no schedule configured -> always a full-chain reference
    assert GenerationPolicy().resume_depth(0.45) == 0


def test_steps_for_resume_never_negative():
    pol = GenerationPolicy(steps_ref=20)
    assert pol.steps_for_resume(0) == 20
    assert pol.steps_for_resume(5) == 15
    assert pol.steps_for_resume(20) == 0
    assert pol.steps_for_resume(25) == 0


def test_latent_depths_validation_at_build():
    with pytest.raises(ValueError):
        build_system(n_nodes=2, corpus_n=16, latent_depths=(0,))
    with pytest.raises(ValueError):
        build_system(n_nodes=2, corpus_n=16, latent_depths=(5, 20))
    system, *_ = build_system(n_nodes=2, corpus_n=16, latent_depths=True)
    assert system.latent_depths == (5, 10, 15)
    assert system.policy.latent_depths == (5, 10, 15)
    system, *_ = build_system(n_nodes=2, corpus_n=16,
                              latent_depths=[15, 5, 5])
    assert system.latent_depths == (5, 15)   # sorted, deduped


# ---------------------------------------------------------------------------
# VDB depth metadata
# ---------------------------------------------------------------------------


def _vecs(n, dim=8, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, dim)).astype(np.float32)


def test_vdb_depth_metadata_defaults_and_roundtrip():
    db = VectorDB(dim=8, capacity=16)
    v = _vecs(3)
    db.add(v, v, np.array([10, 11, 12]), t=1.0)
    slots = np.flatnonzero(db.valid)
    # default: every entry is a finished image that is its own source
    assert (db.depth[slots] == -1).all()
    assert set(db.source_id[slots]) == {10, 11, 12}

    w = _vecs(2, seed=1)
    s2 = db.add(w, w, np.array([20, 21]), t=2.0,
                depths=np.array([5, 10]), source_ids=np.array([10, 10]))
    assert list(db.depth[s2]) == [5, 10]
    assert list(db.source_id[s2]) == [10, 10]

    restored = VectorDB.restore(db.dim, db.capacity, db.snapshot())
    np.testing.assert_array_equal(restored.depth, db.depth)
    np.testing.assert_array_equal(restored.source_id, db.source_id)

    # eviction resets the metadata so freed slots can't alias stale depths
    db.evict_slots(s2)
    assert (db.depth[s2] == -1).all()
    assert (db.source_id[s2] == -1).all()


def test_vdb_fresh_entry_access_count_is_one():
    """Regression: fresh entries used to start at access_count 0 and tied
    as most-evictable under LFU, so a sweep right after insertion evicted
    the newest rows first."""
    db = VectorDB(dim=8, capacity=8)
    v = _vecs(2)
    slots = db.add(v, v, np.array([1, 2]), t=0.0)
    assert (db.access_count[slots] == 1).all()


def test_fused_scan_parity_with_depth_rows():
    """search_batch over a db holding mixed finished/latent rows must be
    bit-identical to a restore of the same snapshot scanned by the same
    program — the depth and source_id columns are host-side metadata the
    fused scan never consumes.  The restored fleet gets its own
    ClusterIndex of the served fleet's shape, so both sides run the
    identical stacked scan (a different scan program may differ in the
    last ulp)."""
    system, emb, _, _ = build_system(n_nodes=2, corpus_n=32,
                                     capacity_per_node=600, seed=0,
                                     latent_depths=True)
    for i, r in enumerate(band_mutation_trace(40, band_fraction=0.5, seed=0)):
        system.serve(r.prompt, seed=i)
    assert any((db.depth[db.valid] >= 0).any() for db in system.dbs)
    q = emb.embed_text(["a medium red circle at the center on a black "
                        "background", "a small blue square at the left on "
                        "a gray background"])
    restored = [VectorDB.restore(db.dim, db.capacity, db.snapshot())
                for db in system.dbs]
    ClusterIndex.from_dbs(restored, mesh_nodes=system.mesh_nodes)
    for db, solo in zip(system.dbs, restored):
        got = db.search_batch(q, 4)
        want = solo.search_batch(q, 4)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# per-depth eviction under one C_max
# ---------------------------------------------------------------------------


def test_per_depth_eviction_protects_deep_latents_on_ties():
    """Identical vectors make every LCU distance tie; the per-depth
    discount must then evict finished images before deep latents (deep
    resumes save the most denoising steps per cached row)."""
    db = VectorDB(dim=8, capacity=16)
    v = np.ones((6, 8), np.float32)
    db.add(v, v, np.arange(100, 106), t=1.0,
           depths=np.array([-1, -1, -1, 5, 10, 15]),
           source_ids=np.array([100, 101, 102, 100, 100, 100]))
    evicted = LCUPolicy().maintain([db], c_max=3)
    gone = set(evicted[0])
    assert gone == {100, 101, 102}           # all finished images
    keep = np.flatnonzero(db.valid)
    assert sorted(db.depth[keep]) == [5, 10, 15]


def test_depth_discount_noop_without_latents():
    """With no latent rows anywhere the depthed scores are bit-identical
    to the raw policy sort."""
    db = VectorDB(dim=8, capacity=16)
    v = _vecs(4, seed=3)
    db.add(v, v, np.arange(4), t=1.0)
    pol = LCUPolicy()
    np.testing.assert_array_equal(pol.depth_scores(db, -1), pol.scores(db))


def test_lfu_recency_tiebreak_evicts_older_insert():
    """Equal access counts break toward evicting the OLDER insert; the
    bounded recency term must never flip a genuine count ordering."""
    db = VectorDB(dim=8, capacity=16)
    v = _vecs(2, seed=4)
    old = db.add(v[:1], v[:1], np.array([1]), t=0.5)[0]
    new = db.add(v[1:], v[1:], np.array([2]), t=5.0)[0]
    s = LFUPolicy().scores(db)
    assert s[old] > s[new]                   # higher score = evicted first
    # a single extra use dominates any recency difference
    db.mark_access(np.array([old]), t=6.0)
    s = LFUPolicy().scores(db)
    assert s[new] > s[old]


# ---------------------------------------------------------------------------
# scheduler strict pairing (bugfix: no silent max(0, ...) clamp)
# ---------------------------------------------------------------------------


def _sched_fixture():
    sched = RequestScheduler(nodes=[NodeInfo(0, speed=1.0),
                                    NodeInfo(1, speed=2.0)])
    dbs = []
    for i in range(2):
        db = VectorDB(dim=512, capacity=8)
        v = _vecs(4, dim=512, seed=5 + i)
        db.add(v, v, np.arange(4), t=0.0)
        dbs.append(db)
    return sched, dbs


def test_scheduler_complete_pairs_normal_path():
    sched, dbs = _sched_fixture()
    q = _vecs(1, dim=512, seed=6)[0]
    d = sched.schedule(q, dbs)
    assert d.fast_path is None
    assert sched.nodes[d.node].queue_depth == 1
    sched.complete(d.node)
    assert sched.nodes[d.node].queue_depth == 0
    # a second release has no matching schedule(): warn, stay at 0
    with pytest.warns(RuntimeWarning, match="queue-depth underflow"):
        sched.complete(d.node)
    assert sched.nodes[d.node].queue_depth == 0


def test_scheduler_complete_pairs_priority_path():
    sched, dbs = _sched_fixture()
    q = _vecs(1, dim=512, seed=7)[0]
    d1 = sched.schedule(q, dbs, quality_tier=True, prompt_key=42)
    sched.complete(d1.node)
    d2 = sched.schedule(q * 0.99, dbs, quality_tier=True, prompt_key=42)
    assert d2.fast_path == "priority"
    assert d2.node == 1                      # fastest node
    assert sched.nodes[1].queue_depth == 1
    sched.complete(d2.node)
    assert sched.nodes[1].queue_depth == 0


def test_scheduler_complete_history_is_noop():
    sched, dbs = _sched_fixture()
    q = _vecs(1, dim=512, seed=8)[0]
    sched.record_result(q, payload_id=7)
    d = sched.schedule(q, dbs)
    assert d.fast_path == "history" and d.node == -1
    depths = [n.queue_depth for n in sched.nodes]
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # no underflow warning either
        sched.complete(d.node)
    assert [n.queue_depth for n in sched.nodes] == depths


# ---------------------------------------------------------------------------
# cost/latency accounting bugfixes
# ---------------------------------------------------------------------------


def test_cost_model_default_rates_wrap_modulo():
    cm = CostModel()
    cm.charge(4, 10.0)                       # node 4 -> rate of node 0
    cm.charge(0, 10.0)
    assert cm.total_cost() == pytest.approx(2 * 10.0 * 0.28 / 3600.0)


def test_cost_model_custom_rates_must_cover_fleet():
    cm = CostModel(gpu_rates=(0.30, 0.20))
    cm.charge(1, 5.0)                        # in range: fine
    with pytest.raises(ValueError, match="no rate in gpu_rates"):
        cm.charge(2, 5.0)
    ok = CostModel(gpu_rates=(0.30, 0.20, 0.10))
    ok.charge(2, 5.0)
    assert ok.total_cost() == pytest.approx(5.0 * 0.10 / 3600.0)


def test_latency_resumed_swaps_noise_for_latent_fetch():
    lm = LatencyModel()
    base = lm.t_embed + lm.t_schedule + lm.t_retrieve
    k, steps = 5, 15
    classic = lm.latency(Route.IMG2IMG, 20)
    resumed = lm.latency(Route.IMG2IMG, steps, resumed=True)
    assert classic == pytest.approx(base + lm.t_noise + 20 * lm.t_step)
    assert resumed == pytest.approx(base + lm.t_latent + steps * lm.t_step)
    assert resumed < classic                 # L_k = t_r + t_latent + (K-k)t_s


# ---------------------------------------------------------------------------
# k=0 resume parity + the end-to-end win
# ---------------------------------------------------------------------------


def test_null_backend_resume_k0_equals_img2img():
    be = NullBackend(res=32)
    prompts = ["a medium red circle at the center on a black background",
               "a large blue square at the left on a gray background"]
    refs = np.stack([np.full((32, 32, 3), 0.3, np.float32),
                     np.full((32, 32, 3), 0.7, np.float32)])
    lat = be.archive_latents_batch(refs, [0, 1], (5, 10), steps_total=20)
    assert lat.shape[0] == 2                 # one slab per depth
    np.testing.assert_array_equal(lat[0], refs)
    out = be.resume_batch(prompts, lat[0], 20, 0, [0, 1])
    np.testing.assert_array_equal(out, be.img2img_batch(prompts, refs,
                                                        20, [0, 1]))


def test_latent_arm_beats_baseline_at_equal_hit_rate():
    """The acceptance property: on the band-mutation workload the latent
    arm serves the SAME routes at the SAME hit rate but strictly fewer
    mean denoising steps — every saved step is a depth resume."""
    reqs = band_mutation_trace(120, band_fraction=0.5, seed=0)
    stats = {}
    for depths in (None, True):
        system, *_ = build_system(n_nodes=2, corpus_n=32,
                                  capacity_per_node=600, seed=0,
                                  latent_depths=depths)
        for i, r in enumerate(reqs):
            system.serve(r.prompt, seed=i)
        stats[bool(depths)] = system.stats
    base, lat = stats[False], stats[True]
    assert lat.route_counts == base.route_counts
    assert lat.hit_rate == pytest.approx(base.hit_rate)
    assert lat.latent_resumes > 0
    assert lat.total_steps < base.total_steps
    saved = base.total_steps - lat.total_steps
    assert saved >= lat.latent_resumes       # every resume skips >= 1 step


def test_latent_resume_latency_accounted_per_depth():
    """Resumed requests must be charged the per-depth Eq. 8 latency, which
    is strictly below the classic img2img latency at the same node speed."""
    system, *_ = build_system(n_nodes=2, corpus_n=32,
                              capacity_per_node=600, seed=0,
                              latent_depths=True)
    lm, pol = system.latency_model, system.policy
    classic = lm.latency(Route.IMG2IMG, pol.steps_ref)
    resumed = [lm.latency(Route.IMG2IMG, pol.steps_for_resume(k),
                          resumed=True) for k in system.latent_depths]
    assert all(r < classic for r in resumed)
    assert sorted(resumed, reverse=True) == resumed   # deeper = faster


def test_diffusion_backend_resume_k0_parity():
    """Real-backend pin of the parity invariant: archiving the depth-0
    latent and resuming from it reproduces the full SDEdit img2img output
    for the same (image, seed) — the latent path is the same chain, just
    split at archive time."""
    import jax
    from repro.configs import get_arch
    from repro.core.embeddings import ProxyClipEmbedder
    from repro.data.synthetic import render_caption
    from repro.models.diffusion import dit as dit_mod
    from repro.models.diffusion import vae as vae_mod
    from repro.runtime.serving import DiffusionBackend

    emb = ProxyClipEmbedder(render_caption)
    dcfg = get_arch("sd15-small").make_config(None)
    net = dit_mod.init_dit(jax.random.key(0), dcfg.net)
    vae = vae_mod.init_vae(jax.random.key(1), dcfg.vae)
    be = DiffusionBackend(net, dcfg.net, vae, dcfg.vae,
                          embed_prompt=lambda p: emb.embed_text([p])[0])
    assert be.supports_latent_resume

    res = dcfg.vae.downsample * dcfg.net.img_res
    prompts = ["a medium red circle at the center on a black background",
               "a small blue square at the left on a gray background"]
    refs = np.stack([render_caption(p, res=res) for p in prompts])
    seeds, steps = [3, 4], 2

    lat = be.archive_latents_batch(refs, seeds, (0, 1), steps_total=steps)
    assert lat.shape[:2] == (2, 2)           # (depths, batch, ...)
    classic = be.img2img_batch(prompts, refs, steps, seeds)
    via_k0 = be.resume_batch(prompts, lat[0], steps, 0, seeds)
    np.testing.assert_allclose(via_k0, classic, atol=1e-5)
    # deeper resume runs fewer steps but stays finite and image-shaped
    via_k1 = be.resume_batch(prompts, lat[1], steps, 1, seeds)
    assert via_k1.shape == classic.shape
    assert np.isfinite(via_k1).all()
