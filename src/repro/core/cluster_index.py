"""Device-resident cross-node retrieval engine (ROADMAP: "cross-node
batched retrieval — one fused scan over all node slabs").

The cluster's whole cache state lives ON DEVICE as one stacked slab

    slabs: (2, nodes, capacity, dim)    # plane 0 = img index, 1 = txt
    valid: (nodes, capacity)            # shared dual-index validity

and is updated INCREMENTALLY: every ``VectorDB.add`` / ``evict_slots``
pushes only the touched rows through a donated functional
``.at[node, slots].set`` — after the one build-time upload there are no
steady-state host→device slab copies (pinned by the transfer-count
test; ``stats["slab_uploads"]`` counts full-slab uploads,
``stats["row_updates"]`` the incremental ones).

Retrieval is ONE fused scan per micro-batch regardless of node count:
``search_batch`` answers every query against its scheduled node's slab
(query→node mask) across both dual-retrieval indexes in a single device
launch — the jnp path is one masked einsum + top-k, the Pallas path is
:func:`repro.kernels.vdb_topk.vdb_topk_sharded` with grid
``(index, node, db_block)`` and the per-query running top-k in VMEM
scratch.  Two all-nodes modes share the same launch structure:
``search_cluster`` (one flat global candidate list per query) and
``search_cluster_nodes`` (a top-k PER node per query — the scan that
score-aware scheduling issues once per micro-batch and the Retrieve
stage then reuses for the chosen node's candidates, collapsing the
Schedule and Retrieve device scans into one).

Each :class:`repro.core.vdb.VectorDB` stays the per-node VIEW over this
shared state: its numpy arrays remain the host source of truth for
eviction bookkeeping / snapshot / restore, and once registered here its
``search``/``search_batch`` delegate to the fused device scan with
identical semantics (same union-dedup, same FIFO-overwrite and eviction
behaviour — pinned by parity tests against the per-node jnp oracle).

``mesh_nodes > 1`` shards all of the above over a 1-D ``("nodes",)``
device mesh: the node axis pads up to a multiple of the mesh size with
masked-invalid nodes, the slabs/validity live as ``NamedSharding``
arrays (specs from :mod:`repro.runtime.partition`), and every scan mode
runs the same per-node kernels inside ``shard_map``
(:func:`repro.kernels.vdb_topk.vdb_topk_sharded_mesh` /
``vdb_topk_pernode_mesh``) so each device scans only its local node
shard.  Only the per-node best-k rows are gathered
(``stats["allgather_bytes"]`` counts them) and the cross-shard merge
(:func:`repro.kernels.vdb_topk.merge_shard_topk`) reproduces the
single-device tie-break bitwise.  Incremental row updates go through
the SAME donated scatter — XLA routes each write to the owning shard,
so the zero steady-state host→device-slab-copy guarantee (and its
stats pins) carries over unchanged.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vdb import VectorDB, _union_topk
from repro.runtime.tracing import span
from repro.utils import l2n, next_pow2


@partial(jax.jit, donate_argnums=(0, 1))
def _apply_rows(slabs, valid, node, slots, img_rows, txt_rows, flags):
    """Write freshly inserted rows into both index planes + validity.
    Donation keeps the update in place — no slab reallocation."""
    slabs = slabs.at[0, node, slots].set(img_rows)
    slabs = slabs.at[1, node, slots].set(txt_rows)
    valid = valid.at[node, slots].set(flags)
    return slabs, valid


@partial(jax.jit, donate_argnums=(0,))
def _apply_valid(valid, node, slots, flags):
    """Eviction only flips validity — the stale vectors stay in place,
    exactly like the numpy slabs (so device state == rebuilt-from-host)."""
    return valid.at[node, slots].set(flags)


@partial(jax.jit, static_argnames=("k", "mask_nodes"))
def _fused_topk(slabs, valid, queries, node_ids, k: int, mask_nodes: bool):
    """jnp path of the fused scan — jitted delegation to the shared test
    oracle (one masked einsum over the flattened cluster, global slot ids
    ``node * cap + col``), numerically the per-node ``_masked_topk_batch``
    restricted to each query's scheduled node."""
    from repro.kernels.ref import vdb_topk_sharded_ref
    with jax.named_scope("vdb_scan"):
        return vdb_topk_sharded_ref(queries, slabs, valid, node_ids, k,
                                    mask_nodes=mask_nodes)


@partial(jax.jit, static_argnames=("k",))
def _fused_topk_pernode(slabs, valid, queries, k: int):
    """jnp path of the per-node scan (one einsum + per-node top-k) —
    jitted delegation to the shared test oracle."""
    from repro.kernels.ref import vdb_topk_pernode_ref
    with jax.named_scope("vdb_scan"):
        return vdb_topk_pernode_ref(queries, slabs, valid, k)


class ClusterIndex:
    """Device-resident dual-index cache state for a whole node fleet."""

    def __init__(self, dim: int, capacities: Sequence[int], *,
                 use_pallas: bool = False,
                 interpret: Optional[bool] = None,
                 mesh_nodes: int = 1):
        self.dim = dim
        self.capacities = [int(c) for c in capacities]
        self.n_nodes = len(self.capacities)
        self.capacity = max(self.capacities) if self.capacities else 0
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.mesh_nodes = int(mesh_nodes)
        self.dbs: List[Optional[VectorDB]] = [None] * self.n_nodes
        self.stats: Dict[str, int] = {
            "slab_uploads": 0, "row_updates": 0, "fused_scans": 0,
            "allgather_bytes": 0}
        if self.mesh_nodes > 1:
            from repro.launch.mesh import make_node_mesh
            self._mesh = make_node_mesh(self.mesh_nodes)
            # pad the node axis to a mesh multiple with masked-invalid
            # nodes (their validity rows stay all-False forever, so their
            # NEG_INF candidates never survive the union)
            self.padded_nodes = (
                -(-max(self.n_nodes, 1) // self.mesh_nodes)
                * self.mesh_nodes)
        else:
            self._mesh = None
            self.padded_nodes = self.n_nodes
        self._slabs = self._shard(
            jnp.zeros((2, self.padded_nodes, self.capacity, dim),
                      jnp.float32), slab=True)
        self._valid = self._shard(
            jnp.zeros((self.padded_nodes, self.capacity), bool), slab=False)

    def _shard(self, arr, *, slab: bool):
        """Commit ``arr`` (jnp or host numpy) to the node mesh — without
        one, a plain device array (``device_put`` IS the one upload when
        ``arr`` is numpy, no staging copy)."""
        if self._mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding

        from repro.runtime.partition import (CLUSTER_SLAB_SPEC,
                                             CLUSTER_VALID_SPEC)
        spec = CLUSTER_SLAB_SPEC if slab else CLUSTER_VALID_SPEC
        return jax.device_put(arr, NamedSharding(self._mesh, spec))

    def per_device_slab_bytes(self) -> int:
        """Bytes of cluster cache state resident on EACH device — the
        quantity the mesh shrinks ~linearly (benchmarks gate on it)."""
        if self._mesh is None:
            return int(self._slabs.nbytes + self._valid.nbytes)
        from repro.runtime.partition import (CLUSTER_SLAB_SPEC,
                                            CLUSTER_VALID_SPEC,
                                            count_sharded_bytes)
        return count_sharded_bytes(
            [self._slabs, self._valid],
            [CLUSTER_SLAB_SPEC, CLUSTER_VALID_SPEC], self._mesh)

    # -- construction -------------------------------------------------------

    @classmethod
    def from_dbs(cls, dbs: Sequence[VectorDB], *,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 mesh_nodes: int = 1) -> "ClusterIndex":
        """Build the stacked device slabs from a fleet's current numpy
        state (ONE upload) and register each db as a view: subsequent
        mutations flow through the incremental row updates.
        ``mesh_nodes > 1`` commits the upload straight to the node mesh —
        still ONE host→device transfer, just scattered across shards."""
        if use_pallas is None:
            use_pallas = any(db.use_pallas for db in dbs)
        if interpret is None:
            interprets = {db.interpret for db in dbs}
            interpret = interprets.pop() if len(interprets) == 1 else None
        ci = cls(dbs[0].dim, [db.capacity for db in dbs],
                 use_pallas=use_pallas, interpret=interpret,
                 mesh_nodes=mesh_nodes)
        img = np.zeros((ci.padded_nodes, ci.capacity, ci.dim), np.float32)
        txt = np.zeros_like(img)
        val = np.zeros((ci.padded_nodes, ci.capacity), bool)
        for ni, db in enumerate(dbs):
            img[ni, :db.capacity] = db.img_vecs
            txt[ni, :db.capacity] = db.txt_vecs
            val[ni, :db.capacity] = db.valid
            ci.dbs[ni] = db
        ci._slabs = ci._shard(np.stack([img, txt]), slab=True)
        ci._valid = ci._shard(val, slab=False)
        ci.stats["slab_uploads"] += 1
        for ni, db in enumerate(dbs):
            db.register_cluster(ci, ni)
        return ci

    # -- incremental mutation (called by the VectorDB views) ----------------

    @staticmethod
    def _pad_slots(slots: np.ndarray) -> Tuple[np.ndarray, int]:
        """Pad the slot vector to a power-of-two bucket (duplicating the
        last slot) so the donated scatter compiles for a handful of
        shapes, not one per insert size."""
        n = len(slots)
        bucket = next_pow2(max(n, 1))
        if bucket != n:
            slots = np.concatenate(
                [slots, np.full(bucket - n, slots[-1], slots.dtype)])
        return slots, n

    def update_rows(self, node: int, slots: np.ndarray,
                    img_rows: np.ndarray, txt_rows: np.ndarray) -> None:
        """A batch of rows was inserted into ``node`` at ``slots``."""
        slots = np.asarray(slots, np.int32)
        if slots.size == 0:
            return
        padded, n = self._pad_slots(slots)
        if n != len(padded):
            img_rows = np.concatenate(
                [img_rows, np.repeat(img_rows[-1:], len(padded) - n, 0)])
            txt_rows = np.concatenate(
                [txt_rows, np.repeat(txt_rows[-1:], len(padded) - n, 0)])
        self._slabs, self._valid = _apply_rows(
            self._slabs, self._valid, jnp.int32(node), jnp.asarray(padded),
            jnp.asarray(img_rows, jnp.float32),
            jnp.asarray(txt_rows, jnp.float32),
            jnp.ones((len(padded),), bool))
        self.stats["row_updates"] += 1

    def invalidate_rows(self, node: int, slots: np.ndarray) -> None:
        """Slots were evicted from ``node`` — only validity flips (the
        numpy slabs keep the stale vectors too)."""
        slots = np.asarray(slots, np.int32)
        if slots.size == 0:
            return
        padded, _ = self._pad_slots(slots)
        self._valid = _apply_valid(self._valid, jnp.int32(node),
                                   jnp.asarray(padded),
                                   jnp.zeros((len(padded),), bool))
        self.stats["row_updates"] += 1

    def refresh_node(self, node: int,
                     db: Optional[VectorDB] = None) -> None:
        """Escape hatch: re-upload one node's slab from its numpy state
        after out-of-band mutation.  Pass ``db`` to REBIND the view to a
        replacement object (e.g. a ``VectorDB.restore`` result) — restore
        returns a new instance, so without the rebind the index would
        keep serving the pre-restore slab."""
        if db is not None:
            old = self.dbs[node]
            if old is not None:
                old.unregister_cluster(self)
            self.dbs[node] = db
            db.register_cluster(self, node)
        db = self.dbs[node]
        if db is None:
            return
        img = np.zeros((self.capacity, self.dim), np.float32)
        txt = np.zeros_like(img)
        val = np.zeros((self.capacity,), bool)
        img[:db.capacity] = db.img_vecs
        txt[:db.capacity] = db.txt_vecs
        val[:db.capacity] = db.valid
        self._slabs = self._slabs.at[0, node].set(jnp.asarray(img))
        self._slabs = self._slabs.at[1, node].set(jnp.asarray(txt))
        self._valid = self._valid.at[node].set(jnp.asarray(val))
        if self._mesh is not None:
            # out-of-jit .at updates may leave XLA-chosen layouts;
            # re-commit to the node mesh (this path is a slab upload
            # anyway — steady-state updates never come through here)
            self._slabs = self._shard(self._slabs, slab=True)
            self._valid = self._shard(self._valid, slab=False)
        self.stats["slab_uploads"] += 1

    # -- search -------------------------------------------------------------

    def _planes(self, index: str) -> Tuple[int, ...]:
        return {"img": (0,), "txt": (1,), "both": (0, 1)}[index]

    @staticmethod
    def _prep_queries(query_vecs: np.ndarray) -> Tuple[np.ndarray, int]:
        """Shared query prep for every scan mode: L2-normalise and pad
        the block to a power-of-two bucket (micro-batch sizes vary, and
        an unpadded (Q, D) shape would re-compile per distinct Q).
        Returns ``(padded_queries, true_batch)``; batch 0 -> (None, 0)."""
        Q = np.atleast_2d(np.asarray(query_vecs, np.float32))
        b = Q.shape[0]
        if b == 0:
            return None, 0
        Qn = l2n(Q)
        bucket = next_pow2(b)
        if bucket != b:
            Qn = np.concatenate(
                [Qn, np.zeros((bucket - b, Qn.shape[1]), np.float32)])
        return Qn, b

    def _scan(self, Qn: np.ndarray, node_ids: Optional[np.ndarray], k: int,
              index: str, mask_nodes: bool, *, per_node: bool = False):
        """The one device launch (every scan mode dispatches here):
        returns (scores, global idx) numpy arrays of shape
        (planes, Qpad, k) — or (planes, nodes, Qpad, k) with
        ``per_node=True``, where the top-k is kept per node and
        ``node_ids``/``mask_nodes`` are ignored."""
        planes = self._planes(index)
        self.stats["fused_scans"] += 1
        slabs = (self._slabs if planes == (0, 1)
                 else self._slabs[planes[0]:planes[0] + 1])
        if self._mesh is not None:
            return self._scan_mesh(Qn, node_ids, k, slabs, mask_nodes,
                                   per_node)
        if per_node:
            if self.use_pallas:
                from repro.kernels.vdb_topk import vdb_topk_pernode
                s, i = vdb_topk_pernode(jnp.asarray(Qn), slabs, self._valid,
                                        k, interpret=self.interpret)
            else:
                s, i = _fused_topk_pernode(slabs, self._valid,
                                           jnp.asarray(Qn), k)
            return np.asarray(s), np.asarray(i)
        nids = jnp.asarray(node_ids, jnp.int32)
        if self.use_pallas:
            from repro.kernels.vdb_topk import vdb_topk_sharded
            s, i = vdb_topk_sharded(jnp.asarray(Qn), slabs, self._valid,
                                    nids, k, mask_nodes=mask_nodes,
                                    interpret=self.interpret)
        else:
            s, i = _fused_topk(slabs, self._valid, jnp.asarray(Qn), nids, k,
                               mask_nodes)
        return np.asarray(s), np.asarray(i)

    def _scan_mesh(self, Qn, node_ids, k: int, slabs, mask_nodes: bool,
                   per_node: bool):
        """Mesh-sharded body of :meth:`_scan` — still the same single
        launch per micro-batch, but run through ``shard_map`` so each
        device scans only its local node shard.  Only the per-shard
        best-k rows come back to the host (counted in
        ``stats["allgather_bytes"]``); the global modes then merge them
        with the single-device tie-break."""
        from repro.kernels.vdb_topk import (merge_shard_topk,
                                            vdb_topk_pernode_mesh,
                                            vdb_topk_sharded_mesh)
        if per_node:
            s, i = vdb_topk_pernode_mesh(
                jnp.asarray(Qn), slabs, self._valid, k, mesh=self._mesh,
                use_pallas=self.use_pallas, interpret=self.interpret)
            s, i = np.asarray(s), np.asarray(i)
            self.stats["allgather_bytes"] += s.nbytes + i.nbytes
            # pad nodes are all-invalid — drop their (NEG_INF, 0) rows
            return s[:, :self.n_nodes], i[:, :self.n_nodes]
        # per-shard k never exceeds the shard's own candidate count; the
        # merged pool (mesh_nodes × k_local) still holds >= k candidates
        n_shard = self.padded_nodes // self.mesh_nodes
        k_local = min(k, n_shard * self.capacity)
        s, i = vdb_topk_sharded_mesh(
            jnp.asarray(Qn), slabs, self._valid,
            jnp.asarray(node_ids, jnp.int32), k_local, mesh=self._mesh,
            mask_nodes=mask_nodes, use_pallas=self.use_pallas,
            interpret=self.interpret)
        s, i = np.asarray(s), np.asarray(i)
        self.stats["allgather_bytes"] += s.nbytes + i.nbytes
        return merge_shard_topk(s, i, k)

    def search_batch(self, query_vecs: np.ndarray, node_ids: Sequence[int],
                     k: int, *, index: str = "both",
                     count_queries: bool = True,
                     ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Fused cross-node dual ANN retrieval: every query against its
        scheduled node, both indexes, ONE device scan for the whole
        micro-batch regardless of how many nodes it touches.

        Returns one ``(scores, slots)`` pair per query with
        ``VectorDB.search`` semantics: deduped union across indexes,
        invalid/masked candidates dropped, scores descending, slots LOCAL
        to the query's node.
        """
        Qn, b = self._prep_queries(query_vecs)
        if b == 0:
            return []
        nids = np.asarray(list(node_ids), np.int32)
        if count_queries:
            for ni in nids:
                if self.dbs[ni] is not None:
                    self.dbs[ni].query_count += 1
        if len(Qn) != b:
            nids = np.concatenate([nids, np.zeros(len(Qn) - b, np.int32)])
        k = min(k, self.capacity)
        s, i = self._scan(Qn, nids, k, index, mask_nodes=True)
        out = []
        for row in range(b):
            local = i[:, row] - nids[row] * self.capacity
            out.append(_union_topk(list(s[:, row]), list(local)))
        return out

    def search_cluster(self, query_vecs: np.ndarray, k: int, *,
                       index: str = "both",
                       ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """All-nodes flat mode: each query scans the WHOLE cluster in the
        same single launch and gets ONE global candidate list; returned
        slots are global ids ``node * capacity + col``
        (``node = slot // capacity``).

        Note for routing callers: a single hot node can monopolise the
        global top-k, hiding every other node's best match — score-aware
        scheduling therefore uses :meth:`search_cluster_nodes`, which
        keeps a top-k PER node at identical slab traffic."""
        Qn, b = self._prep_queries(query_vecs)
        if b == 0:
            return []
        k = min(k, self.capacity * max(self.n_nodes, 1))
        s, i = self._scan(Qn, np.zeros(len(Qn), np.int32), k, index,
                          mask_nodes=False)
        return [_union_topk(list(s[:, row]), list(i[:, row]))
                for row in range(b)]

    def search_cluster_nodes(self, query_vecs: np.ndarray, k: int, *,
                             index: str = "both",
                             ) -> List[List[Tuple[np.ndarray, np.ndarray]]]:
        """All-nodes PER-NODE mode — the schedule+retrieve fusion scan.

        ONE device launch (jnp: one einsum + per-node top-k; Pallas:
        :func:`repro.kernels.vdb_topk.vdb_topk_pernode`) answers every
        query against EVERY node's slab across both dual-retrieval
        indexes.  Returns ``out[query][node] = (scores, slots)`` with
        exactly :meth:`VectorDB.search` semantics per node (deduped union
        across indexes, invalid candidates dropped, scores descending,
        slots LOCAL to that node) — so ``out[q][n]`` is bit-identical to
        what a masked ``search_batch`` on node ``n`` would have returned,
        and the Retrieve stage can reuse the chosen node's row without a
        second scan while the scheduler routes on every node's best
        match.

        The launch and the host merge are one ``scan`` span: ``queries``
        is the true batch, ``rows`` the slots the launch reads (every
        slot of every node, valid or not).
        """
        Qn, b = self._prep_queries(query_vecs)
        if b == 0:
            return []
        k = min(k, self.capacity)
        with span("scan", queries=b, rows=self.n_nodes * self.capacity):
            s, i = self._scan(Qn, None, k, index, mask_nodes=False,
                              per_node=True)     # (planes, nodes, Qpad, k)
            out: List[List[Tuple[np.ndarray, np.ndarray]]] = []
            for row in range(b):
                per_node = []
                for node in range(self.n_nodes):
                    local = i[:, node, row] - node * self.capacity
                    per_node.append(_union_topk(list(s[:, node, row]),
                                                list(local)))
                out.append(per_node)
        return out

    # -- derived state ------------------------------------------------------

    def node_vectors(self) -> np.ndarray:
        """L2-normalised node representation vectors (Eq. 6) from the
        per-db running centroids — O(nodes·dim), no slab reduction.
        Delegates to the scheduler's single implementation."""
        from repro.core.scheduler import RequestScheduler
        return RequestScheduler.node_vectors(
            [db if db is not None else VectorDB(self.dim, 0)
             for db in self.dbs])

    # -- introspection (tests / debugging) ----------------------------------

    def device_state(self) -> Tuple[np.ndarray, np.ndarray]:
        """Device slabs/validity pulled to host, sliced to the REAL nodes
        (mesh padding stripped) so it compares directly against
        :meth:`rebuild_reference` at any mesh size."""
        return (np.asarray(self._slabs)[:, :self.n_nodes],
                np.asarray(self._valid)[:self.n_nodes])

    def rebuild_reference(self) -> Tuple[np.ndarray, np.ndarray]:
        """What the device state SHOULD be, rebuilt from the numpy views
        (parity oracle for the incremental-update tests)."""
        img = np.zeros((self.n_nodes, self.capacity, self.dim), np.float32)
        txt = np.zeros_like(img)
        val = np.zeros((self.n_nodes, self.capacity), bool)
        for ni, db in enumerate(self.dbs):
            if db is None:
                continue
            img[ni, :db.capacity] = db.img_vecs
            txt[ni, :db.capacity] = db.txt_vecs
            val[ni, :db.capacity] = db.valid
        return np.stack([img, txt]), val
