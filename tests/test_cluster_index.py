"""PR 4: device-resident cross-node retrieval engine.

Pins the ClusterIndex contracts:

* fused cross-node ``search_batch`` == the per-node jnp oracle
  (``_masked_topk_batch`` + union) for every query, across node mixes
  including empty and over-capacity nodes and non-uniform capacities;
* the Pallas ``vdb_topk_sharded`` kernel == its jnp ref, masked and
  all-nodes modes;
* incremental device-slab state == rebuilt-from-numpy after randomized
  add/evict/overwrite sequences;
* the steady-state serve path performs ZERO host→device slab uploads
  and exactly ONE fused scan per micro-batch;
* the vectorised ``_union_topk`` and the cached ``centroid()`` keep
  their pre-PR semantics.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.cluster_index import ClusterIndex
from repro.core.vdb import VectorDB, _union_topk
from repro.kernels.ref import (vdb_topk_pernode_ref, vdb_topk_ref,
                               vdb_topk_sharded_ref)
from repro.kernels.vdb_topk import (NEG_INF, resolve_interpret, vdb_topk,
                                    vdb_topk_pernode, vdb_topk_sharded)
from repro.launch.serve import build_system


def _unit(rng, n, d):
    v = rng.normal(size=(n, d)).astype(np.float32)
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


def _mixed_fleet(rng, dim=24):
    """Node mix the fused scan must survive: empty node, partially full,
    exactly full, overfilled (FIFO overwrite), non-uniform capacity."""
    dbs = [VectorDB(dim, 32, name="empty"),
           VectorDB(dim, 32, name="partial"),
           VectorDB(dim, 16, name="full"),
           VectorDB(dim, 48, name="overfull")]
    dbs[1].add(_unit(rng, 10, dim), _unit(rng, 10, dim), np.arange(10), 0.0)
    dbs[2].add(_unit(rng, 16, dim), _unit(rng, 16, dim), np.arange(16), 0.0)
    dbs[3].add(_unit(rng, 60, dim), _unit(rng, 60, dim), np.arange(60), 0.0)
    return dbs


# ---------------------------------------------------------------------------
# fused scan vs per-node oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("index", ["both", "img", "txt"])
def test_fused_vs_per_node_oracle_parity(index):
    rng = np.random.default_rng(0)
    dbs = _mixed_fleet(rng)
    Q = _unit(rng, 7, 24)
    node_ids = [0, 1, 2, 3, 3, 1, 2]
    # oracle rows from the standalone per-node path, BEFORE attaching
    oracle = [dbs[n].search_batch(q[None], 8, index=index)[0]
              for q, n in zip(Q, node_ids)]
    ci = ClusterIndex.from_dbs(dbs)
    fused = ci.search_batch(Q, node_ids, 8, index=index)
    for (o_s, o_l), (f_s, f_l) in zip(oracle, fused):
        np.testing.assert_array_equal(o_l, f_l)
        np.testing.assert_allclose(o_s, f_s, rtol=1e-5, atol=1e-6)


def test_fused_pallas_vs_oracle_parity():
    rng = np.random.default_rng(1)
    dbs = _mixed_fleet(rng)
    Q = _unit(rng, 5, 24)
    node_ids = [1, 2, 3, 1, 3]
    oracle = [dbs[n].search_batch(q[None], 6)[0]
              for q, n in zip(Q, node_ids)]
    ci = ClusterIndex.from_dbs(dbs, use_pallas=True, interpret=True)
    fused = ci.search_batch(Q, node_ids, 6)
    for (o_s, o_l), (f_s, f_l) in zip(oracle, fused):
        np.testing.assert_array_equal(o_l, f_l)
        np.testing.assert_allclose(o_s, f_s, rtol=1e-4, atol=1e-5)


def test_empty_node_returns_no_candidates():
    rng = np.random.default_rng(2)
    dbs = _mixed_fleet(rng)
    ci = ClusterIndex.from_dbs(dbs)
    (scores, slots), = ci.search_batch(_unit(rng, 1, 24), [0], 4)
    assert len(scores) == 0 and len(slots) == 0


def test_attached_vdb_search_delegates_with_identical_results():
    rng = np.random.default_rng(3)
    dbs = _mixed_fleet(rng)
    q = _unit(rng, 1, 24)[0]
    legacy = [db.search(q, k=5) for db in dbs]
    ci = ClusterIndex.from_dbs(dbs)
    qc0 = [db.query_count for db in dbs]
    for db, (l_s, l_l) in zip(dbs, legacy):
        c_s, c_l = db.search(q, k=5)           # now the fused cluster path
        np.testing.assert_array_equal(l_l, c_l)
        np.testing.assert_allclose(l_s, c_s, rtol=1e-5, atol=1e-6)
    assert [db.query_count for db in dbs] == [c + 1 for c in qc0]
    assert ci.stats["fused_scans"] == len(dbs)


def test_search_cluster_all_nodes_mode_matches_flat_oracle():
    rng = np.random.default_rng(4)
    dbs = _mixed_fleet(rng)
    ci = ClusterIndex.from_dbs(dbs)
    Q = _unit(rng, 3, 24)
    rows = ci.search_cluster(Q, 5)
    slabs, valid = ci.device_state()
    for q, (scores, gslots) in zip(Q, rows):
        # oracle: per-plane top-k over the flattened cluster, then union
        s_ref, i_ref = vdb_topk_sharded_ref(
            jnp.asarray(q[None]), jnp.asarray(slabs), jnp.asarray(valid),
            jnp.zeros((1,), jnp.int32), 5, mask_nodes=False)
        o_s, o_l = _union_topk([np.asarray(s_ref[p][0]) for p in range(2)],
                               [np.asarray(i_ref[p][0]) for p in range(2)])
        np.testing.assert_array_equal(o_l, gslots)
        np.testing.assert_allclose(o_s, scores, rtol=1e-5, atol=1e-6)
        # global ids decompose into (node, col) within capacity
        assert ((gslots // ci.capacity) < ci.n_nodes).all()


# ---------------------------------------------------------------------------
# the sharded Pallas kernel vs its jnp ref
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mask_nodes", [True, False])
@pytest.mark.parametrize("qn,nodes,cap,k,block", [
    (4, 2, 32, 4, 16), (8, 3, 64, 8, 64), (2, 4, 24, 3, 16)])
def test_sharded_kernel_matches_ref(qn, nodes, cap, k, block, mask_nodes):
    rng = np.random.default_rng(qn * 100 + nodes * 10 + k)
    slabs = rng.normal(size=(2, nodes, cap, 16)).astype(np.float32)
    valid = rng.random((nodes, cap)) < 0.7
    Q = _unit(rng, qn, 16)
    nids = rng.integers(0, nodes, size=qn).astype(np.int32)
    s_k, i_k = vdb_topk_sharded(jnp.asarray(Q), jnp.asarray(slabs),
                                jnp.asarray(valid), jnp.asarray(nids), k,
                                block_n=block, mask_nodes=mask_nodes,
                                interpret=True)
    s_r, i_r = vdb_topk_sharded_ref(jnp.asarray(Q), jnp.asarray(slabs),
                                    jnp.asarray(valid), jnp.asarray(nids), k,
                                    mask_nodes=mask_nodes)
    s_k, i_k, s_r, i_r = map(np.asarray, (s_k, i_k, s_r, i_r))
    real = np.isfinite(s_r) & (s_r > NEG_INF / 2)
    np.testing.assert_array_equal(np.where(real, i_k, -1),
                                  np.where(real, i_r, -1))
    np.testing.assert_allclose(s_k[real], s_r[real], rtol=1e-5, atol=1e-6)
    # kernel sentinel: masked candidates sit at NEG_INF, never -inf
    assert np.isfinite(s_k).all()


@pytest.mark.parametrize("mode", ["single", "masked", "global", "pernode"])
@pytest.mark.parametrize("cap,block", [(200, 128), (300, 512), (130, 128)])
def test_first_max_pick_breaks_ties_like_ref(cap, block, mode):
    """Every slab row repeats one of five vectors, so scores tie in large
    groups that straddle block boundaries; capacities are not multiples
    of the 128-lane block.  The kernel's first-max pick must return the
    same ids as ``jax.lax.top_k`` (lowest slot first among ties)."""
    rng = np.random.default_rng(cap)
    base = _unit(rng, 5, 16)
    slabs = base[rng.integers(0, 5, size=(2, 3, cap))]
    valid = rng.random((3, cap)) < 0.9
    Q = np.concatenate([base[:3], _unit(rng, 1, 16)])
    nids = np.array([0, 2, 1, 2], np.int32)
    k = 12
    args = [jnp.asarray(a) for a in (Q, slabs, valid, nids)]
    if mode == "single":
        got = vdb_topk(args[0], args[1][0, 0], args[2][0], k, block_n=block,
                       interpret=True)
        want = vdb_topk_ref(args[0], args[1][0, 0], args[2][0], k)
    elif mode == "pernode":
        got = vdb_topk_pernode(*args[:3], k, block_n=block, interpret=True)
        want = vdb_topk_pernode_ref(*args[:3], k)
    else:
        masked = mode == "masked"
        got = vdb_topk_sharded(*args, k, block_n=block, mask_nodes=masked,
                               interpret=True)
        want = vdb_topk_sharded_ref(*args, k, mask_nodes=masked)
    s_k, i_k, s_r, i_r = map(np.asarray, (*got, *want))
    real = np.isfinite(s_r) & (s_r > NEG_INF / 2)
    assert real.sum() > 0
    np.testing.assert_array_equal(np.where(real, i_k, -1),
                                  np.where(real, i_r, -1))
    # two programs (Pallas tile dot vs einsum): scores may differ by 1 ulp
    np.testing.assert_allclose(s_k[real], s_r[real], rtol=1e-5, atol=1e-6)


def test_interpret_default_is_backend_aware():
    # on this container (no TPU) None must resolve to interpret mode and
    # produce the same results as an explicit interpret=True
    import jax
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    rng = np.random.default_rng(7)
    db = rng.normal(size=(32, 8)).astype(np.float32)
    valid = rng.random(32) < 0.8
    q = _unit(rng, 2, 8)
    s_auto, i_auto = vdb_topk(jnp.asarray(q), jnp.asarray(db),
                              jnp.asarray(valid), 4)
    s_int, i_int = vdb_topk(jnp.asarray(q), jnp.asarray(db),
                            jnp.asarray(valid), 4, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_auto), np.asarray(i_int))
    np.testing.assert_array_equal(np.asarray(s_auto), np.asarray(s_int))


# ---------------------------------------------------------------------------
# incremental device state
# ---------------------------------------------------------------------------


def test_incremental_state_matches_rebuild_after_random_mutations():
    rng = np.random.default_rng(11)
    dim = 12
    dbs = [VectorDB(dim, c) for c in (8, 16, 16)]
    ci = ClusterIndex.from_dbs(dbs)
    uploads0 = ci.stats["slab_uploads"]
    for step in range(60):
        ni = int(rng.integers(0, len(dbs)))
        db = dbs[ni]
        op = rng.integers(0, 3)
        if op < 2:          # add (incl. overwrite-oldest when full)
            n = int(rng.integers(1, db.capacity + 3))  # > capacity allowed
            db.add(_unit(rng, n, dim), _unit(rng, n, dim),
                   np.arange(n) + step * 1000, t=float(step))
        else:               # evict a random live subset
            live = np.flatnonzero(db.valid)
            if len(live):
                db.evict_slots(rng.choice(
                    live, size=int(rng.integers(1, len(live) + 1)),
                    replace=False))
    dev_slabs, dev_valid = ci.device_state()
    ref_slabs, ref_valid = ci.rebuild_reference()
    np.testing.assert_array_equal(dev_valid, ref_valid)
    np.testing.assert_array_equal(dev_slabs, ref_slabs)
    assert ci.stats["slab_uploads"] == uploads0      # rows only, no slabs
    assert ci.stats["row_updates"] > 0


def test_refresh_node_resyncs_out_of_band_mutation():
    rng = np.random.default_rng(12)
    dbs = [VectorDB(8, 8) for _ in range(2)]
    dbs[0].add(_unit(rng, 4, 8), _unit(rng, 4, 8), np.arange(4), 0.0)
    ci = ClusterIndex.from_dbs(dbs)
    dbs[0].img_vecs[0] = 0.0                         # behind the index's back
    ci.refresh_node(0)
    dev_slabs, dev_valid = ci.device_state()
    ref_slabs, ref_valid = ci.rebuild_reference()
    np.testing.assert_array_equal(dev_slabs, ref_slabs)
    np.testing.assert_array_equal(dev_valid, ref_valid)


def test_refresh_node_rebinds_restored_vdb():
    """`VectorDB.restore` returns a NEW object; refresh_node(node, db=...)
    must rebind the view so the index serves the restored state and
    subsequent mutations flow from the new object."""
    rng = np.random.default_rng(13)
    dbs = [VectorDB(8, 8) for _ in range(2)]
    dbs[0].add(_unit(rng, 4, 8), _unit(rng, 4, 8), np.arange(4), 0.0)
    snap = dbs[0].snapshot()
    ci = ClusterIndex.from_dbs(dbs)
    dbs[0].evict_slots(np.array([0, 1, 2, 3]))       # diverge, then restore
    restored = VectorDB.restore(8, 8, snap)
    ci.refresh_node(0, db=restored)
    assert ci.dbs[0] is restored
    dev_slabs, dev_valid = ci.device_state()
    ref_slabs, ref_valid = ci.rebuild_reference()
    np.testing.assert_array_equal(dev_slabs, ref_slabs)
    np.testing.assert_array_equal(dev_valid, ref_valid)
    # the old object no longer feeds the index; the new one does
    restored.add(_unit(rng, 1, 8), _unit(rng, 1, 8), np.array([99]), 1.0)
    dev_slabs, dev_valid = ci.device_state()
    ref_slabs, ref_valid = ci.rebuild_reference()
    np.testing.assert_array_equal(dev_slabs, ref_slabs)
    np.testing.assert_array_equal(dev_valid, ref_valid)
    q = restored.img_vecs[restored.valid][0]
    (scores, slots), = ci.search_batch(q[None], [0], 3)
    assert restored.valid[slots].all()


# ---------------------------------------------------------------------------
# serve-path integration: one scan per micro-batch, zero slab uploads
# ---------------------------------------------------------------------------


def _prompts(system, n, seed=0):
    from repro.core.trace import RequestTrace
    return [r.prompt for r in RequestTrace(seed=seed).generate(n)]


def test_retrieve_stage_issues_exactly_one_scan_per_microbatch(monkeypatch):
    """Centroid mode: the Retrieve stage's masked scan is the batch's one
    device scan.  (Score mode fuses Schedule+Retrieve into one
    ``search_cluster_nodes`` scan — pinned in
    ``tests/test_scheduling_score.py``.)"""
    system, _, _, _ = build_system(n_nodes=3, corpus_n=90,
                                   capacity_per_node=60, routing="centroid")
    ci = system.cluster_index
    assert ci is not None
    calls = []
    orig = ci.search_batch
    monkeypatch.setattr(ci, "search_batch",
                        lambda *a, **kw: calls.append(a) or orig(*a, **kw))
    # the per-node path must never run on the serve path
    monkeypatch.setattr(
        VectorDB, "search_batch",
        lambda self, *a, **kw: pytest.fail("per-node search on serve path"))
    prompts = _prompts(system, 8)
    results = system.serve_batch(prompts, seeds=list(range(8)))
    assert len(results) == 8
    assert len(calls) == 1                 # ONE fused scan for the batch
    nodes_touched = {d for d in calls[0][1]}
    assert len(nodes_touched) >= 1


def test_steady_state_serve_has_zero_slab_uploads():
    system, _, _, _ = build_system(n_nodes=3, corpus_n=90,
                                   capacity_per_node=60)
    ci = system.cluster_index
    prompts = _prompts(system, 24, seed=3)
    system.serve_batch(prompts[:8], seeds=list(range(8)))      # warmup
    uploads = ci.stats["slab_uploads"]
    scans = ci.stats["fused_scans"]
    for lo in (8, 16):
        system.serve_batch(prompts[lo:lo + 8],
                           seeds=list(range(lo, lo + 8)))
    assert ci.stats["slab_uploads"] == uploads   # ZERO steady-state uploads
    assert ci.stats["fused_scans"] >= scans + 2  # but the scans did run
    assert ci.stats["row_updates"] > 0           # archives flowed as rows


def test_serve_parity_with_and_without_cluster_index():
    """The fused engine is a pure perf change: routes, nodes and hit
    stats match a system running the per-node fallback on the same
    trace.  Centroid mode on both sides — score routing REQUIRES the
    cluster index (dropping it falls back to centroid routing), so the
    retrieval engine's pure-perf contract is a centroid-mode property."""
    kw = dict(n_nodes=3, corpus_n=90, capacity_per_node=60,
              routing="centroid")
    sys_a, _, _, _ = build_system(**kw)
    sys_b, _, _, _ = build_system(**kw)
    sys_b.cluster_index = None                   # force per-node fallback
    prompts = _prompts(sys_a, 20, seed=5)
    ra = [sys_a.serve(p, seed=i) for i, p in enumerate(prompts)]
    rb = [sys_b.serve(p, seed=i) for i, p in enumerate(prompts)]
    for a, b in zip(ra, rb):
        assert a.route == b.route and a.node == b.node
        np.testing.assert_array_equal(a.image, b.image)
    assert sys_a.stats.route_counts == sys_b.stats.route_counts
    assert sys_a.stats.cache_hits == sys_b.stats.cache_hits


# ---------------------------------------------------------------------------
# satellites: vectorised _union_topk + cached centroid
# ---------------------------------------------------------------------------


def test_union_topk_drops_sentinels_and_keeps_best_per_slot():
    scores = [np.array([0.9, -np.inf, 0.5, -2e30], np.float32),
              np.array([0.7, 0.9, np.inf, np.nan], np.float32)]
    slots = [np.array([3, 1, 2, 0]), np.array([3, 5, 6, 7])]
    s, l = _union_topk(scores, slots)
    assert l.tolist() == [3, 5, 2]            # best-per-slot, desc order
    np.testing.assert_allclose(s, [0.9, 0.9, 0.5])


def test_union_topk_empty_and_all_masked():
    s, l = _union_topk([], [])
    assert len(s) == 0 and len(l) == 0
    s, l = _union_topk([np.array([-np.inf, -1e30], np.float32)],
                       [np.array([0, 1])])
    assert len(s) == 0 and len(l) == 0
    assert s.dtype == np.float32 and l.dtype == np.int64


def test_union_topk_matches_dict_reference_randomized():
    rng = np.random.default_rng(21)
    for _ in range(50):
        rows = rng.integers(1, 3)
        score_rows, slot_rows = [], []
        for _ in range(rows):
            n = rng.integers(1, 12)
            sc = rng.normal(size=n).astype(np.float32)
            sc[rng.random(n) < 0.2] = -np.inf
            sc[rng.random(n) < 0.1] = -1e30
            score_rows.append(sc)
            slot_rows.append(rng.integers(0, 8, size=n))
        best = {}
        for sc, sl in zip(score_rows, slot_rows):
            for c, s_ in zip(sc, sl):
                if np.isfinite(c) and c > -1e29 and \
                        (s_ not in best or c > best[s_]):
                    best[int(s_)] = float(c)
        got_s, got_l = _union_topk(score_rows, slot_rows)
        assert dict(zip(got_l.tolist(), got_s.tolist())) == pytest.approx(best)
        assert list(got_s) == sorted(got_s, reverse=True)


def test_add_partial_overflow_evicts_oldest_without_duplicate_slots():
    """Regression: a batch insert into a PARTIALLY full db (0 < free < n)
    must land every row on a distinct slot and overwrite the oldest VALID
    entries — not re-pick already-free slots (which silently dropped rows
    and kept entries FIFO should have evicted)."""
    rng = np.random.default_rng(24)
    db = VectorDB(8, 4)
    db.add(_unit(rng, 4, 8), _unit(rng, 4, 8), np.array([100, 101, 102, 103]),
           t=0.0)
    db.evict_slots(np.array([0, 1]))             # 2 free, 2 valid (102, 103)
    db.insert_time[2] = 0.5                      # 102 older than 103
    db.insert_time[3] = 1.0
    slots = db.add(_unit(rng, 3, 8), _unit(rng, 3, 8),
                   np.array([200, 201, 202]), t=2.0)
    assert len(set(slots.tolist())) == 3         # no duplicate slots
    alive = set(db.payload_ids[db.valid].tolist())
    assert alive == {103, 200, 201, 202}         # oldest valid (102) evicted
    np.testing.assert_allclose(db.centroid(),
                               db.img_vecs[db.valid].mean(axis=0),
                               rtol=1e-5, atol=1e-7)


def test_centroid_cache_tracks_mutations():
    rng = np.random.default_rng(22)
    db = VectorDB(10, 16)
    for step in range(30):
        if rng.random() < 0.6 or db.size == 0:
            n = int(rng.integers(1, 5))
            db.add(_unit(rng, n, 10), _unit(rng, n, 10),
                   np.arange(n) + step * 100, t=float(step))
        else:
            live = np.flatnonzero(db.valid)
            db.evict_slots(rng.choice(live, size=1))
        if db.size:
            np.testing.assert_allclose(
                db.centroid(), db.img_vecs[db.valid].mean(axis=0),
                rtol=1e-5, atol=1e-7)
        else:
            np.testing.assert_array_equal(db.centroid(), np.zeros(10))


def test_centroid_invalidated_on_restore():
    rng = np.random.default_rng(23)
    db = VectorDB(6, 8)
    db.add(_unit(rng, 5, 6), _unit(rng, 5, 6), np.arange(5), 0.0)
    snap = db.snapshot()
    db2 = VectorDB.restore(6, 8, snap)
    np.testing.assert_allclose(db2.centroid(), db.centroid(),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(db2.centroid(),
                               db2.img_vecs[db2.valid].mean(axis=0),
                               rtol=1e-5, atol=1e-7)
