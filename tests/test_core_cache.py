"""The paper's cache layer: VDB, storage classifier, scheduler, LCU —
unit behaviour + hypothesis property tests on the invariants."""
from __future__ import annotations

import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # offline container: seeded-random shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.kmeans import cluster_sizes, kmeans_assign, kmeans_fit
from repro.core.lcu import (FIFOPolicy, LCUPolicy, LFUPolicy, LRUPolicy,
                            POLICIES)
from repro.core.scheduler import NodeInfo, RequestScheduler
from repro.core.storage_classifier import StorageClassifier
from repro.core.vdb import BlobStore, VectorDB

import jax.numpy as jnp


def _unit(rng, n, d):
    v = rng.normal(size=(n, d)).astype(np.float32)
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# VectorDB
# ---------------------------------------------------------------------------


def test_vdb_add_search_roundtrip():
    rng = np.random.default_rng(0)
    db = VectorDB(dim=16, capacity=32)
    vecs = _unit(rng, 10, 16)
    slots = db.add(vecs, vecs, np.arange(10), t=0.0)
    assert db.size == 10 and len(slots) == 10
    scores, got = db.search(vecs[3], k=1, index="img")
    assert got[0] == slots[3]
    assert scores[0] > 0.999


def test_vdb_dual_index_union():
    rng = np.random.default_rng(1)
    db = VectorDB(dim=8, capacity=16)
    img = _unit(rng, 6, 8)
    txt = _unit(rng, 6, 8)
    db.add(img, txt, np.arange(6), t=0.0)
    scores, slots = db.search(txt[2], k=3, index="both")
    assert len(slots) == len(set(slots.tolist()))  # de-duplicated union
    assert len(slots) <= 6


def test_vdb_overwrite_oldest_when_full():
    rng = np.random.default_rng(2)
    db = VectorDB(dim=8, capacity=4)
    a = _unit(rng, 4, 8)
    db.add(a, a, np.arange(4), t=0.0)
    b = _unit(rng, 2, 8)
    db.add(b, b, np.array([100, 101]), t=1.0)
    assert db.size == 4
    assert set([100, 101]).issubset(set(db.payload_ids[db.valid].tolist()))


def test_vdb_overwrite_targets_exactly_the_oldest():
    """FIFO pressure valve: when full, inserts overwrite the entries with
    the OLDEST insert_time, never newer ones."""
    rng = np.random.default_rng(12)
    db = VectorDB(dim=8, capacity=4)
    for i in range(4):                       # distinct insert times 0..3
        v = _unit(rng, 1, 8)
        db.add(v, v, np.array([i]), t=float(i))
    nv = _unit(rng, 2, 8)
    db.add(nv, nv, np.array([100, 101]), t=10.0)
    alive = set(db.payload_ids[db.valid].tolist())
    assert alive == {2, 3, 100, 101}         # payloads 0 and 1 (oldest) gone
    assert db.size == 4


def test_vdb_add_batch_larger_than_capacity():
    """A single insert bigger than the slab keeps size == capacity and the
    newest entries win the collided slots."""
    rng = np.random.default_rng(13)
    db = VectorDB(dim=8, capacity=4)
    v = _unit(rng, 6, 8)
    db.add(v, v, np.arange(6), t=0.0)
    assert db.size == 4
    assert set(db.payload_ids[db.valid].tolist()) == {2, 3, 4, 5}


def test_vdb_evict_returns_payloads():
    rng = np.random.default_rng(3)
    db = VectorDB(dim=8, capacity=8)
    v = _unit(rng, 5, 8)
    slots = db.add(v, v, np.arange(50, 55), t=0.0)
    payloads = db.evict_slots(slots[:2])
    assert sorted(payloads.tolist()) == [50, 51]
    assert db.size == 3


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 30), k=st.integers(1, 8), seed=st.integers(0, 99))
def test_vdb_search_scores_sorted_and_valid(n, k, seed):
    """Property: scores descend; returned slots are valid; k caps results."""
    rng = np.random.default_rng(seed)
    db = VectorDB(dim=8, capacity=64)
    v = _unit(rng, n, 8)
    db.add(v, v, np.arange(n), t=0.0)
    q = _unit(rng, 1, 8)[0]
    scores, slots = db.search(q, k=k)
    assert list(scores) == sorted(scores, reverse=True)
    assert db.valid[slots].all()
    assert len(slots) <= 2 * k


# ---------------------------------------------------------------------------
# K-means / storage classifier
# ---------------------------------------------------------------------------


def test_kmeans_separates_clear_clusters():
    rng = np.random.default_rng(4)
    a = rng.normal(0, 0.05, (40, 4)) + np.array([1, 0, 0, 0])
    b = rng.normal(0, 0.05, (40, 4)) + np.array([-1, 0, 0, 0])
    x = np.concatenate([a, b]).astype(np.float32)
    state = kmeans_fit(jnp.asarray(x), k=2, iters=10)
    asg = np.asarray(state.assignment)
    assert len(set(asg[:40])) == 1 and len(set(asg[40:])) == 1
    assert asg[0] != asg[40]
    sizes = np.asarray(cluster_sizes(state.assignment, 2))
    assert sizes.sum() == 80


def test_kmeans_inertia_decreases_with_iters():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(100, 8)).astype(np.float32))
    i1 = float(kmeans_fit(x, k=4, iters=1).inertia)
    i10 = float(kmeans_fit(x, k=4, iters=10).inertia)
    assert i10 <= i1 + 1e-5


def test_storage_classifier_builds_consistent_fleet(fleet, corpus, embedder):
    dbs, blob, cls, img_vecs, _, _ = fleet
    assert sum(db.size for db in dbs) == len(img_vecs)
    # every stored vector is nearest to its own node's centroid
    asg = cls.assign(img_vecs)
    for ni, db in enumerate(dbs):
        if db.size:
            stored = db.img_vecs[db.valid]
            a, _ = kmeans_assign(jnp.asarray(stored),
                                 jnp.asarray(cls.centroids))
            assert (np.asarray(a) == ni).mean() > 0.99
    assert cls.modal_consistency is not None
    assert cls.modal_consistency > 0.5  # paper Fig. 6b: high cross-modal agreement


def test_failed_node_reassignment(fleet):
    dbs, blob, cls, img_vecs, _, _ = fleet
    total_before = sum(db.size for db in dbs)
    moved = dbs[1].size
    cls.reassign_failed_node(dbs, failed=1, t=9.0)
    assert dbs[1].size == 0
    assert sum(db.size for db in dbs) == total_before
    del moved


# ---------------------------------------------------------------------------
# request scheduler (Eq. 6 + fast paths)
# ---------------------------------------------------------------------------


def test_scheduler_routes_to_most_similar_node(fleet):
    dbs, _, cls, img_vecs, _, _ = fleet
    sched = RequestScheduler(nodes=[NodeInfo(i) for i in range(4)],
                             balance_weight=0.0)
    # a query ON a node centroid must route to that node
    for ni in range(4):
        if dbs[ni].size == 0:
            continue
        q = dbs[ni].centroid()
        d = sched.schedule(q, dbs)
        assert d.node == ni
        sched.complete(d.node)


def test_scheduler_history_fast_path(fleet):
    dbs, _, _, img_vecs, _, _ = fleet
    sched = RequestScheduler(nodes=[NodeInfo(i) for i in range(4)])
    q = img_vecs[0]
    sched.record_result(q, payload_id=777)
    d = sched.schedule(q, dbs)
    assert d.fast_path == "history" and d.history_payload == 777


def test_scheduler_priority_fast_path(fleet):
    dbs, _, _, img_vecs, _, _ = fleet
    nodes = [NodeInfo(0, speed=1.0), NodeInfo(1, speed=2.0),
             NodeInfo(2, speed=0.5), NodeInfo(3, speed=1.0)]
    sched = RequestScheduler(nodes=nodes)
    q = img_vecs[1]
    d1 = sched.schedule(q, dbs, quality_tier=True, prompt_key=42)
    assert d1.fast_path is None          # first occurrence: normal path
    d2 = sched.schedule(q + 0.31, dbs, quality_tier=True, prompt_key=42)
    assert d2.fast_path == "priority"
    assert d2.node == 1                  # fastest node


def test_scheduler_skips_failed_nodes(fleet):
    dbs, _, _, img_vecs, _, _ = fleet
    sched = RequestScheduler(nodes=[NodeInfo(i) for i in range(4)])
    sched.mark_failed(2)
    for i in range(8):
        d = sched.schedule(img_vecs[i], dbs)
        assert d.node != 2
        sched.complete(d.node)


def test_scheduler_load_balances():
    rng = np.random.default_rng(6)
    dbs = []
    for i in range(2):
        db = VectorDB(8, 16)
        v = _unit(rng, 4, 8)
        db.add(v, v, np.arange(4), t=0)
        dbs.append(db)
    sched = RequestScheduler(nodes=[NodeInfo(0), NodeInfo(1)],
                             balance_weight=10.0)  # heavy penalty
    q = dbs[0].centroid()
    first = sched.schedule(q, dbs)       # goes to node 0, queue grows
    second = sched.schedule(q, dbs)      # penalty pushes to node 1
    assert {first.node, second.node} == {0, 1}


# ---------------------------------------------------------------------------
# eviction policies (Algorithm 2 + baselines, Fig. 19)
# ---------------------------------------------------------------------------


def _db_with(rng, n=12, d=8):
    db = VectorDB(d, 32)
    v = _unit(rng, n, d)
    db.add(v, v, np.arange(n), t=0.0)
    return db


def test_lcu_evicts_farthest_from_centroid():
    rng = np.random.default_rng(7)
    db = VectorDB(4, 16)
    tight = rng.normal(0, 0.01, (8, 4)) + np.array([1.0, 0, 0, 0])
    outlier = np.array([[-1.0, 0, 0, 0]])
    vecs = np.concatenate([tight, outlier]).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=-1, keepdims=True)
    db.add(vecs, vecs, np.arange(9), t=0.0)
    evicted = LCUPolicy().maintain([db], c_max=8)
    assert evicted[0].tolist() == [8]    # the outlier goes first


def test_lru_lfu_fifo_orderings():
    rng = np.random.default_rng(8)
    db = _db_with(rng, n=4)
    db.mark_access(np.array([0, 1]), t=5.0)     # 2,3 least recently used
    db.mark_access(np.array([0]), t=6.0)        # 0 most frequent
    ev_lru = LRUPolicy().maintain([_copy_db(db)], c_max=3)
    assert ev_lru[0][0] in (2, 3)
    ev_lfu = LFUPolicy().maintain([_copy_db(db)], c_max=3)
    assert ev_lfu[0][0] in (1, 2, 3)            # not the frequent slot 0
    db2 = _copy_db(db)
    db2.insert_time[:4] = [3.0, 2.0, 1.0, 0.0]
    ev_fifo = FIFOPolicy().maintain([db2], c_max=3)
    assert ev_fifo[0][0] == 3                   # oldest insert


def _copy_db(db):
    new = VectorDB(db.dim, db.capacity)
    for attr in ("img_vecs", "txt_vecs", "valid", "insert_time",
                 "last_access", "access_count", "payload_ids"):
        setattr(new, attr, getattr(db, attr).copy())
    return new


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 50), cmax=st.integers(0, 30),
       policy=st.sampled_from(sorted(POLICIES)))
def test_policies_always_reach_capacity(seed, cmax, policy):
    """Property (Algorithm 2 line 10): after maintain, Σ|D_k| ≤ C_max, and
    nothing is evicted when already within capacity."""
    rng = np.random.default_rng(seed)
    dbs = [_db_with(rng, n=rng.integers(1, 12)) for _ in range(3)]
    before = sum(db.size for db in dbs)
    evicted = POLICIES[policy].maintain(dbs, c_max=cmax)
    after = sum(db.size for db in dbs)
    if before <= cmax:
        assert evicted == {} and after == before
    else:
        assert after == cmax
        n_evicted = sum(len(v) for v in evicted.values())
        assert n_evicted == before - cmax


def test_scheduler_invalidate_payloads_drops_history_entries():
    rng = np.random.default_rng(14)
    sched = RequestScheduler(nodes=[NodeInfo(0)])
    vecs = _unit(rng, 3, 512)
    for i, v in enumerate(vecs):
        sched.record_result(v, payload_id=100 + i)
    sched.invalidate_payloads([101])
    assert sched._hist_payloads == [100, 102]
    assert sched._hist_vecs.shape[0] == 2
    # the evicted entry no longer fast-paths; the survivors still do
    assert sched._history_lookup(vecs[1]) is None
    assert sched._history_lookup(vecs[0]) == 100
    assert sched._history_lookup(vecs[2]) == 102


def test_maintain_keeps_history_cache_consistent():
    """CacheGenius.maintain (Algorithm 2 + §IV-G sync deletion): after an
    eviction sweep, every surviving history entry must still resolve in the
    blob store, and evicted payloads must be gone from the history cache —
    otherwise a later near-duplicate prompt would dereference a deleted
    image."""
    from repro.launch.serve import build_system
    from repro.core.trace import RequestTrace

    system, _, _, _ = build_system(n_nodes=2, corpus_n=40,
                                   capacity_per_node=40, seed=0)
    system.maintenance_interval = 10 ** 9          # manual maintain only
    reqs = list(RequestTrace(seed=4).generate(40))
    for i, r in enumerate(reqs):
        system.serve(r.prompt, seed=i)
    assert len(system.scheduler._hist_payloads) > 0
    system.cache_capacity = system.total_size - 10  # force eviction
    evicted = system.maintain()
    assert sum(len(v) for v in evicted.values()) >= 10
    blob_ids = set(system.blob_store._blobs)
    evicted_ids = {int(p) for v in evicted.values() for p in v}
    assert not (set(system.scheduler._hist_payloads) & evicted_ids)
    assert all(p in blob_ids for p in system.scheduler._hist_payloads)
    # replaying the whole trace must not dereference a deleted blob
    for i, r in enumerate(reqs):
        system.serve(r.prompt, seed=1000 + i)


# every per-slot column of a VectorDB (the rows and their metadata)
_COLUMNS = ("img_vecs", "txt_vecs", "valid", "payload_ids", "depth",
            "source_id", "insert_time", "last_access", "access_count")


def _columns(db):
    return {c: getattr(db, c).copy() for c in _COLUMNS}


def _assert_device_matches_host(ci):
    """The ClusterIndex slabs and validity equal the numpy rows they are
    built from: the agreement the benchmark's scan check relies on."""
    slabs, valid = ci.device_state()
    want_slabs, want_valid = ci.rebuild_reference()
    np.testing.assert_array_equal(slabs, want_slabs)
    np.testing.assert_array_equal(valid, want_valid)


def _per_row_sweep(policy, dbs, c_max):
    """Reference: the per-row sweep the vectorised one replaced. One
    (score, node, slot) tuple per valid row, a stable sort highest score
    first, then one ``evict_slots`` per node."""
    depth_norm = max((int(db.depth[db.valid].max(initial=-1))
                      for db in dbs), default=-1)
    entries = []
    total = 0
    for ni, db in enumerate(dbs):
        total += db.size
        s = policy.depth_scores(db, depth_norm)
        for slot in np.flatnonzero(db.valid):
            entries.append((float(s[slot]), ni, int(slot)))
    if total <= c_max:
        return {}
    entries.sort(key=lambda e: e[0], reverse=True)
    doomed = {}
    for _, ni, slot in entries[:total - c_max]:
        doomed.setdefault(ni, []).append(slot)
    return {ni: dbs[ni].evict_slots(np.array(slots, np.int64))
            .astype(np.int64)
            for ni, slots in doomed.items()}


def _tied_fleet(latent: bool):
    """Three nodes whose rows tie under every policy's key: each node
    holds four groups of three identical vectors (equal LCU distances),
    inserted in two batches at shared clocks (FIFO, LRU, LFU ties across
    nodes too), with some rows used again and one hole in the slots.
    With ``latent`` each group is a latent depth of its own."""
    rng = np.random.default_rng(21)
    dbs = []
    for ni in range(3):
        db = VectorDB(8, 24)
        vecs = np.repeat(_unit(rng, 4, 8), 3, axis=0)
        pids = np.arange(12, dtype=np.int64) + 100 * ni
        depths = (np.repeat([-1, 2, 4, 6], 3) if latent
                  else np.full(12, -1))
        for lo, hi, t in ((0, 6, 1.0), (6, 12, 2.0)):
            db.add(vecs[lo:hi], vecs[lo:hi], pids[lo:hi], t=t,
                   depths=depths[lo:hi])
        db.mark_access(np.array([0, 4, 8]), t=3.0)
        db.evict_slots(np.array([5]))
        dbs.append(db)
    return dbs


def _clone(db):
    new = VectorDB.restore(db.dim, db.capacity, db.snapshot())
    # the running centroid, bit for bit (a rebuild sums in another order)
    new._cent_sum, new._cent_count = db._cent_sum.copy(), db._cent_count
    return new


def _tie_splitting_budget(policy, dbs):
    """A ``c_max`` whose cut falls between two rows of equal score."""
    depth_norm = max(int(db.depth[db.valid].max(initial=-1)) for db in dbs)
    ranked = sorted((float(s) for db in dbs
                     for s in policy.depth_scores(db, depth_norm)[db.valid]),
                    reverse=True)
    cuts = [k for k in range(1, len(ranked)) if ranked[k - 1] == ranked[k]]
    assert cuts, "the fleet has no tie to split"
    return len(ranked) - cuts[len(cuts) // 2]


@pytest.mark.parametrize("latent", [False, True],
                         ids=["images", "latents"])
@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_sweep_picks_the_per_row_loops_victims(policy, latent):
    """Over budget, the vectorised sweep evicts exactly what the per-row
    loop did, node for node and in the same order, also where the cut
    splits a tie; it writes no row, and the device slabs still equal the
    host rows after it and after a following add."""
    from repro.core.cluster_index import ClusterIndex

    pol = POLICIES[policy]
    dbs = _tied_fleet(latent)
    ref_dbs = [_clone(db) for db in dbs]
    ci = ClusterIndex.from_dbs(dbs)
    c_max = _tie_splitting_budget(pol, dbs)
    vecs = [(db.img_vecs.copy(), db.txt_vecs.copy()) for db in dbs]

    got = pol.maintain(dbs, c_max)
    want = _per_row_sweep(pol, ref_dbs, c_max)
    assert {n: v.tolist() for n, v in got.items()} == {
        n: v.tolist() for n, v in want.items()}
    assert len(got) > 1                        # victims on several nodes
    assert sum(db.size for db in dbs) == c_max
    for db, ref, (img, txt) in zip(dbs, ref_dbs, vecs):
        for c in _COLUMNS:
            np.testing.assert_array_equal(getattr(db, c), getattr(ref, c))
        assert db.img_vecs.tobytes() == img.tobytes()
        assert db.txt_vecs.tobytes() == txt.tobytes()
    _assert_device_matches_host(ci)

    freed = np.flatnonzero(~dbs[1].valid)[:2]
    new = _unit(np.random.default_rng(5), 2, 8)
    slots = dbs[1].add(new, new, np.array([900, 901]), t=4.0)
    np.testing.assert_array_equal(slots, freed)
    _assert_device_matches_host(ci)


def test_under_budget_sweep_scores_nothing_and_changes_nothing(monkeypatch):
    """A fleet within its budget is neither scored nor touched: every
    row, metadata column and device slab is bit-identical after the
    sweep, and the device slabs still equal the host rows."""
    from repro.launch.serve import build_system

    system, _, _, _ = build_system(n_nodes=2, corpus_n=40,
                                   capacity_per_node=40, seed=0)
    assert system.cluster_index is not None
    assert 0 < system.total_size <= system.cache_capacity
    calls = []
    scores = system.eviction.scores

    def spy(db):
        calls.append(db)
        return scores(db)
    monkeypatch.setattr(system.eviction, "scores", spy)
    before = [_columns(db) for db in system.dbs]
    slabs, valid = system.cluster_index.device_state()

    assert system.maintain() == {}
    assert calls == []
    for db, cols in zip(system.dbs, before):
        for c, v in cols.items():
            assert getattr(db, c).tobytes() == v.tobytes(), c
    after = system.cluster_index.device_state()
    assert after[0].tobytes() == slabs.tobytes()
    assert after[1].tobytes() == valid.tobytes()
    _assert_device_matches_host(system.cluster_index)


def test_lcu_scores_equal_the_full_slab_distances():
    """LCU scores only the valid rows, into a new array: the same float32
    distances as over the whole slab, -inf elsewhere, rows untouched."""
    rng = np.random.default_rng(9)
    db = _db_with(rng, n=12, d=512)
    db.evict_slots(np.array([3, 7]))
    img = db.img_vecs.copy()
    mu = db.centroid()
    want = np.where(db.valid,
                    np.linalg.norm(db.img_vecs - mu[None, :], axis=-1),
                    -np.inf)
    got = LCUPolicy().scores(db)
    assert got.dtype == want.dtype
    assert got.tobytes() == want.tobytes()
    assert db.img_vecs.tobytes() == img.tobytes()


def test_blob_store_consistency():
    blob = BlobStore()
    a = blob.put(np.ones((2, 2)))
    b = blob.put(np.zeros((2, 2)))
    assert len(blob) == 2
    blob.delete(a)
    assert len(blob) == 1
    assert blob.get(b).sum() == 0
