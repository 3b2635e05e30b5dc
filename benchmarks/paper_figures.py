"""One function per paper table/figure.  Each returns a JSON-serializable
dict; ``benchmarks.run`` executes all of them and writes
``experiments/results.json`` + the EXPERIMENTS.md source tables.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.core.latency_model import CostModel, LatencyModel
from repro.core.lcu import POLICIES
from repro.core.policy import GenerationPolicy, Route
from repro.core.trace import RequestTrace
from repro.data.synthetic import (SceneSpec, caption_of, make_corpus,
                                  render_caption, render_scene)
from repro.models.diffusion import dit as dit_mod
from repro.models.diffusion import vae as vae_mod
from repro.models.diffusion.sampler import ddim_sample, sdedit_sample


# ---------------------------------------------------------------------------
# Fig. 1 — PSNR evolution: text-to-image vs image-to-image
# ---------------------------------------------------------------------------


def fig1_psnr_steps(n_scenes: int = 12) -> Dict:
    """i2i (from a structurally similar reference) reaches a given PSNR in
    fewer denoising steps than t2i — the paper's founding observation."""
    stack = C.get_stack()
    dcfg, vcfg = C._dit_cfg(), C._vae_cfg()
    eps_fn = dit_mod.make_eps_fn(stack.dit_params, dcfg)
    rng = np.random.default_rng(0)
    step_grid = [5, 10, 15, 20, 25, 30]
    curves = {"t2i": {s: [] for s in step_grid},
              "i2i": {s: [] for s in step_grid}}

    @jax.jit
    def decode(z):
        return vae_mod.decode(stack.vae_params, vcfg, z / C.LATENT_SCALE)

    for i in range(n_scenes):
        # target scene + a same-structure different-color reference
        target = C.render_caption(stack.corpus_captions[i], C.IMG_RES) \
            if False else None
        from repro.data.synthetic import random_spec, COLORS
        spec = random_spec(rng)
        target_img = render_scene(spec, C.IMG_RES)
        other_color = rng.choice([c for c in COLORS if c != spec.color])
        ref_spec = SceneSpec(spec.shape, other_color, spec.background,
                             spec.size, spec.position)
        ref_img = render_scene(ref_spec, C.IMG_RES)
        ctx = jnp.asarray(stack.embedder.embed_text(
            [caption_of(spec)]), jnp.float32)
        mean, _ = vae_mod.encode(stack.vae_params, vcfg,
                                 jnp.asarray(ref_img)[None])
        z_ref = mean * C.LATENT_SCALE
        for steps in step_grid:
            z_t2i = ddim_sample(eps_fn, C.SCHED,
                                (1, dcfg.img_res, dcfg.img_res, dcfg.in_ch),
                                ctx, jax.random.key(i), steps=steps)
            img_t2i = np.asarray(decode(z_t2i)[0])
            z_i2i = sdedit_sample(eps_fn, C.SCHED, z_ref, ctx,
                                  jax.random.key(100 + i), steps=steps,
                                  strength=0.6)
            img_i2i = np.asarray(decode(z_i2i)[0])
            curves["t2i"][steps].append(C.psnr(img_t2i, target_img))
            curves["i2i"][steps].append(C.psnr(img_i2i, target_img))

    out = {"steps": step_grid,
           "t2i_psnr": [float(np.mean(curves["t2i"][s])) for s in step_grid],
           "i2i_psnr": [float(np.mean(curves["i2i"][s])) for s in step_grid]}
    # the paper's claim: i2i at 20 steps ≥ t2i at 30 steps
    out["claim_i2i20_vs_t2i30"] = out["i2i_psnr"][3] >= out["t2i_psnr"][5]
    return out


# ---------------------------------------------------------------------------
# Table I — quality metrics across methods
# ---------------------------------------------------------------------------


def table1_quality(n_requests: int = 150) -> Dict:
    stack = C.get_stack()
    reqs = C.trace_prompts(n_requests)
    _, _, specs = make_corpus(len(stack.corpus_images), res=C.IMG_RES, seed=0)
    clf = C.ShapeClassifier(stack.scorer, stack.corpus_images, specs)
    real = stack.corpus_images[:n_requests]

    methods = {}
    methods["stable-diffusion"] = C.run_plain_sd(stack, reqs)
    methods["sd-tiny"] = C.run_plain_sd(stack, reqs, tiny=True)
    methods["gpt-cache"] = C.run_retrieval_baseline(stack, reqs, embed="bert")
    methods["pinecone"] = C.run_retrieval_baseline(stack, reqs, embed="clip")
    methods["nirvana"] = C.run_nirvana(stack, reqs)
    methods["cachegenius"], _ = C.run_cachegenius(stack, reqs)
    methods["cachegenius_wo_cmp"], _ = C.run_cachegenius(
        stack, reqs, eviction="FIFO", capacity_per_node=10 ** 6)
    methods["cachegenius_wo_rs"], _ = C.run_cachegenius(
        stack, reqs, use_scheduler=False)

    table = {}
    for name, res in methods.items():
        table[name] = {
            "clip_score": C.clip_score(stack.scorer, res.prompts,
                                       res.images),
            "pick_score": C.pick_score(stack.scorer, res.prompts,
                                       res.images),
            "inception_score": C.inception_score(clf, res.images),
            "fid": C.fid_proxy(stack.scorer, real, res.images),
            "mean_latency": float(res.latencies.mean()),
        }
    return {"classifier_train_acc": clf.train_acc, "methods": table}


# ---------------------------------------------------------------------------
# Table II + Fig. 13 — latency distribution
# ---------------------------------------------------------------------------


def table2_latency(n_requests: int = 200) -> Dict:
    stack = C.get_stack()
    reqs = C.trace_prompts(n_requests, seed=7)
    rows = {}
    runs = {
        "gpt-cache": C.run_retrieval_baseline(stack, reqs, embed="bert"),
        "pinecone": C.run_retrieval_baseline(stack, reqs, embed="clip"),
        "nirvana": C.run_nirvana(stack, reqs),
        "sd-tiny": C.run_plain_sd(stack, reqs, tiny=True),
        "stable-diffusion": C.run_plain_sd(stack, reqs),
        "cachegenius": C.run_cachegenius(stack, reqs)[0],
    }
    for name, res in runs.items():
        lat = res.latencies
        med = float(np.median(lat))
        rows[name] = {
            "mean_s": float(lat.mean()),
            "p50": med,
            "p90_over_median": float(np.percentile(lat, 90) / med),
            "p95_over_median": float(np.percentile(lat, 95) / med),
            "p99_over_median": float(np.percentile(lat, 99) / med),
        }
    sd, cg = rows["stable-diffusion"]["mean_s"], rows["cachegenius"]["mean_s"]
    return {"rows": rows,
            "latency_reduction_vs_sd": 1.0 - cg / sd,
            "paper_claims_41pct": True}


# ---------------------------------------------------------------------------
# Fig. 12 — similarity-score CDF
# ---------------------------------------------------------------------------


def fig12_cdf(n_requests: int = 150) -> Dict:
    stack = C.get_stack()
    reqs = C.trace_prompts(n_requests, seed=3)
    out = {}
    runs = {
        "gpt-cache": C.run_retrieval_baseline(stack, reqs, embed="bert"),
        "pinecone": C.run_retrieval_baseline(stack, reqs, embed="clip"),
        "stable-diffusion": C.run_plain_sd(stack, reqs),
        "cachegenius": C.run_cachegenius(stack, reqs)[0],
    }
    for name, res in runs.items():
        s = np.sort(res.scores * 100.0)
        out[name] = {
            "frac_above_50": float(np.mean(s > 50.0)),
            "p25": float(np.percentile(s, 25)),
            "p50": float(np.percentile(s, 50)),
            "p75": float(np.percentile(s, 75)),
        }
    return out


# ---------------------------------------------------------------------------
# Fig. 14 — request-scheduler ablation
# ---------------------------------------------------------------------------


def fig14_scheduler(n_requests: int = 150) -> Dict:
    stack = C.get_stack()
    reqs = C.trace_prompts(n_requests, seed=11)
    with_rs, sys_with = C.run_cachegenius(stack, reqs, use_scheduler=True)
    without_rs, sys_wo = C.run_cachegenius(stack, reqs, use_scheduler=False)
    return {
        "with_rs_mean_latency": float(with_rs.latencies.mean()),
        "without_rs_mean_latency": float(without_rs.latencies.mean()),
        "with_rs_hit_rate": sys_with.stats.hit_rate,
        "without_rs_hit_rate": sys_wo.stats.hit_rate,
        "improvement": 1.0 - float(with_rs.latencies.mean()
                                   / without_rs.latencies.mean()),
    }


# ---------------------------------------------------------------------------
# Fig. 15 — similarity-threshold sweep
# ---------------------------------------------------------------------------


def fig15_threshold(n_requests: int = 120) -> Dict:
    stack = C.get_stack()
    reqs = C.trace_prompts(n_requests, seed=13)
    rows = []
    for hi in (0.3, 0.4, 0.5, 0.6, 0.7):
        pol = GenerationPolicy(lo=hi - 0.1, hi=hi)
        res, system = C.run_cachegenius(stack, reqs, policy=pol)
        rows.append({
            "threshold": hi,
            "mean_latency": float(res.latencies.mean()),
            "clip_score": C.clip_score(stack.scorer, res.prompts,
                                       res.images),
            "hit_rate": system.stats.hit_rate,
        })
    return {"rows": rows}


# ---------------------------------------------------------------------------
# Fig. 16 — denoising-step sweep (img2img K)
# ---------------------------------------------------------------------------


def fig16_steps(n_requests: int = 100) -> Dict:
    stack = C.get_stack()
    reqs = C.trace_prompts(n_requests, seed=17)
    rows = []
    for k in (5, 10, 15, 20, 25, 30):
        pol = GenerationPolicy(steps_ref=k)
        res, _ = C.run_cachegenius(stack, reqs, policy=pol)
        rows.append({
            "k_steps": k,
            "mean_latency": float(res.latencies.mean()),
            "clip_score": C.clip_score(stack.scorer, res.prompts,
                                       res.images),
        })
    return {"rows": rows, "default_k": 20}


# ---------------------------------------------------------------------------
# Table III — prompt-optimizer ablation
# ---------------------------------------------------------------------------


def table3_prompt_opt(n_requests: int = 120) -> Dict:
    stack = C.get_stack()
    reqs = C.trace_prompts(n_requests, seed=19)
    _, _, specs = make_corpus(len(stack.corpus_images), res=C.IMG_RES, seed=0)
    clf = C.ShapeClassifier(stack.scorer, stack.corpus_images, specs)
    real = stack.corpus_images[:n_requests]
    with_po, _ = C.run_cachegenius(stack, reqs, use_prompt_optimizer=True)
    without_po, _ = C.run_cachegenius(stack, reqs, use_prompt_optimizer=False)
    return {
        "with_po": {"inception_score": C.inception_score(clf, with_po.images),
                    "fid": C.fid_proxy(stack.scorer, real, with_po.images),
                    "mean_latency": float(with_po.latencies.mean())},
        "without_po": {"inception_score": C.inception_score(
                           clf, without_po.images),
                       "fid": C.fid_proxy(stack.scorer, real,
                                          without_po.images),
                       "mean_latency": float(without_po.latencies.mean())},
    }


# ---------------------------------------------------------------------------
# Fig. 17 — cost over a 5000-task stream
# ---------------------------------------------------------------------------


def fig17_cost(n_tasks: int = 5000, sample: int = 200) -> Dict:
    """Route mix measured on a sampled trace, extrapolated to 5000 tasks
    with the paper's AutoDL rates."""
    stack = C.get_stack()
    reqs = C.trace_prompts(sample, seed=23)
    res, system = C.run_cachegenius(stack, reqs)
    lm = system.latency_model
    scale = n_tasks / sample
    cg_cost = system.cost_model.total_cost() * scale

    base = CostModel()
    for _ in range(sample):
        base.charge(0, system.policy.steps_full * lm.t_step)
    sd_cost = base.total_cost() * scale
    return {"n_tasks": n_tasks,
            "cachegenius_cost": cg_cost,
            "stable_diffusion_cost": sd_cost,
            "cost_reduction": 1.0 - cg_cost / sd_cost,
            "paper_claims_48pct": True}


# ---------------------------------------------------------------------------
# Fig. 18 — throughput vs number of edge nodes
# ---------------------------------------------------------------------------


def fig18_throughput(n_requests: int = 120) -> Dict:
    stack = C.get_stack()
    reqs = C.trace_prompts(n_requests, seed=29)
    speeds8 = [1.0, 1.0, 0.82, 0.45, 1.0, 0.45, 0.45, 0.45]
    rows = []
    for n_nodes in (1, 2, 4, 8):
        res, system = C.run_cachegenius(stack, reqs, n_nodes=n_nodes)
        # system throughput = aggregate node-seconds available / per-request
        # busy time, from the measured route mix (Eq. 8 terms)
        busy = res.latencies.mean()
        tp_cg = sum(speeds8[:n_nodes]) / busy
        full = system.latency_model.latency(Route.TXT2IMG,
                                            system.policy.steps_full)
        tp_sd = sum(speeds8[:n_nodes]) / full
        rows.append({"nodes": n_nodes,
                     "cachegenius_tput": tp_cg,
                     "stable_diffusion_tput": tp_sd})
    r4 = rows[2]["cachegenius_tput"]
    r8sd = rows[3]["stable_diffusion_tput"]
    return {"rows": rows, "cg4_vs_sd8": r4 / r8sd}


# ---------------------------------------------------------------------------
# Serving throughput vs micro-batch size (beyond-paper: batched serve path)
# ---------------------------------------------------------------------------


def serving_batch_throughput() -> Dict:
    """Measured requests/sec of the batched end-to-end path: the queue
    drains through ``CacheGenius.serve_batch``, so same-route requests in a
    micro-batch share one retrieval scan and one padded denoiser call."""
    stack = C.get_stack()
    return C.run_serving_throughput(stack, batch_sizes=C.BATCH_SIZES)


def serving_latency_curve() -> Dict:
    """Latency vs offered load: p50/p95 true queue delay + throughput of
    continuous batching vs fixed-drain on the same Poisson arrival trace
    at each rate, plus the bursty-trace worst case."""
    stack = C.get_stack()
    return C.run_serving_latency_curve(stack, arrival_rates=C.ARRIVAL_RATES)


# ---------------------------------------------------------------------------
# Retrieval scan: fused cross-node device scan vs per-node loop
# ---------------------------------------------------------------------------


def retrieval_scan(batch: int = 8, dim: int = 512, k: int = 8,
                   iters: int = 5) -> Dict:
    """The paper's retrieval hot path at fleet scale: wall time and
    effective scan bandwidth of ONE fused ``ClusterIndex.search_batch``
    (device-resident stacked slabs, query→node mask) vs the pre-PR-4
    per-node loop (one ``VectorDB.search_batch`` per touched node, each
    re-uploading its slab), across ``C.NODE_COUNTS`` × ``C.CACHE_CAPACITIES``.

    Mesh sizes > 1 in ``C.MESH_NODES`` (``--mesh-nodes``) add a SHARDED
    arm per shape: the same fused scan with the slabs partitioned over a
    1-D "nodes" device mesh (each device scans only its local node
    shard; only per-node best-k rows are gathered).  Each sharded row
    records per-device slab bytes, all-gather bytes, and fused-vs-
    sharded wall, and gates ``sharded_parity_ok`` (bitwise-identical
    retrieval + routing results) and ``sharded_shrinks_slab``
    (per-device bytes < the unsharded slab).  Requires the backend to
    expose >= mesh devices (``benchmarks.run --mesh-nodes`` forces host
    devices before jax initialises, and fails when it cannot).

    Stack-free: runs on synthetic vectors, so CI can smoke it without
    training the diffusion stack."""
    from repro.core.cluster_index import ClusterIndex
    from repro.core.vdb import VectorDB

    def bench(fn):
        fn()                                  # warmup / compile
        best = np.inf
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    rows: List[Dict] = []
    mesh_rows: List[Dict] = []
    for n_nodes in C.NODE_COUNTS:
        for cap in C.CACHE_CAPACITIES:
            rng = np.random.default_rng(1000 * n_nodes + cap)
            dbs = [VectorDB(dim, cap, name=f"bench{i}")
                   for i in range(n_nodes)]
            for db in dbs:
                v = rng.normal(size=(cap, dim)).astype(np.float32)
                t = rng.normal(size=(cap, dim)).astype(np.float32)
                db.add(v, t, np.arange(cap), t=0.0)
            Q = rng.normal(size=(batch, dim)).astype(np.float32)
            node_ids = rng.integers(0, n_nodes, size=batch)
            by_node: Dict[int, List[int]] = {}
            for qi, ni in enumerate(node_ids):
                by_node.setdefault(int(ni), []).append(qi)

            def loop_scan():                  # pre-cluster per-node path
                for ni, qs in by_node.items():
                    dbs[ni].search_batch(Q[qs], k)

            # time the loop BEFORE attaching the cluster (attaching makes
            # VectorDB.search_batch delegate to the fused scan)
            t_loop = bench(loop_scan)
            ci = ClusterIndex.from_dbs(dbs)
            t_fused = bench(lambda: ci.search_batch(Q, node_ids, k))
            scan_bytes = 2 * n_nodes * cap * dim * 4  # img+txt slabs, f32
            rows.append({
                "nodes": n_nodes, "capacity": cap,
                "touched_nodes": len(by_node),
                "per_node_loop_s": t_loop, "fused_scan_s": t_fused,
                "speedup": t_loop / t_fused,
                "loop_gbps": scan_bytes / t_loop / 1e9,
                "fused_gbps": scan_bytes / t_fused / 1e9,
            })
            base = ci.search_batch(Q, node_ids, k, count_queries=False)
            for m in C.MESH_NODES:
                if m <= 1:
                    continue
                # identical second fleet: the first one's dbs are bound
                # to the unsharded index (both would receive updates)
                rng2 = np.random.default_rng(1000 * n_nodes + cap)
                dbs_m = [VectorDB(dim, cap, name=f"bench{i}m")
                         for i in range(n_nodes)]
                for db in dbs_m:
                    v = rng2.normal(size=(cap, dim)).astype(np.float32)
                    t = rng2.normal(size=(cap, dim)).astype(np.float32)
                    db.add(v, t, np.arange(cap), t=0.0)
                cim = ClusterIndex.from_dbs(dbs_m, mesh_nodes=m)
                t_sharded = bench(
                    lambda: cim.search_batch(Q, node_ids, k,
                                             count_queries=False))
                ag0 = cim.stats["allgather_bytes"]
                got = cim.search_batch(Q, node_ids, k, count_queries=False)
                ag_bytes = cim.stats["allgather_bytes"] - ag0
                parity = len(base) == len(got) and all(
                    np.array_equal(bs, gs) and np.array_equal(bi, gi)
                    for (bs, bi), (gs, gi) in zip(base, got))
                mesh_rows.append({
                    "nodes": n_nodes, "capacity": cap, "mesh_nodes": m,
                    "fused_scan_s": t_fused, "sharded_scan_s": t_sharded,
                    "single_device_slab_bytes": ci.per_device_slab_bytes(),
                    "per_device_slab_bytes": cim.per_device_slab_bytes(),
                    "allgather_bytes_per_scan": ag_bytes,
                    "sharded_parity_ok": parity,
                })
    wins = [r for r in rows if r["nodes"] >= 4 and r["capacity"] >= 2048]
    return {"rows": rows, "mesh_rows": mesh_rows,
            "fused_beats_loop_everywhere":
                all(r["speedup"] > 1.0 for r in rows),
            # None when the sweep didn't include the acceptance shape
            "fused_beats_loop_at_4x2048":
                all(r["speedup"] > 1.0 for r in wins) if wins else None,
            # sharded-arm gates: None when no mesh>1 arm ran
            "sharded_parity_ok":
                all(r["sharded_parity_ok"] for r in mesh_rows)
                if mesh_rows else None,
            "sharded_shrinks_slab":
                all(r["per_device_slab_bytes"]
                    < r["single_device_slab_bytes"] for r in mesh_rows)
                if mesh_rows else None}


# ---------------------------------------------------------------------------
# Scheduling quality: score-aware vs centroid routing (beyond-paper)
# ---------------------------------------------------------------------------


def scheduling_quality(corpus_n: int = 120, n_nodes: int = 4,
                       max_batch: int = 8) -> Dict:
    """Score-aware vs centroid routing on a skewed-cache trace across
    offered loads: cache hit-rate, true queue delay (p50/p95) and mean
    Eq. 8 latency per arrival rate.

    The skew: corpus rows are shuffled round-robin across nodes, so
    every node's centroid is ~the global mean (Eq. 6 routing is blind)
    while each prompt's best reference lives on exactly one node —
    exactly the regime where routing on the TRUE best match from the
    cluster-wide fused scan pays.  Each cached scene is requested once
    via a Poisson arrival process at each rate; both modes replay the
    identical trace on identical fleets.

    Stack-free: NullBackend + proxy embedder, so CI can smoke it without
    training the diffusion stack."""
    from repro.core.embeddings import ProxyClipEmbedder
    from repro.core.system import CacheGenius
    from repro.core.trace import poisson_arrivals
    from repro.core.vdb import BlobStore, VectorDB
    from repro.launch.serve import NullBackend
    from repro.runtime.serving import ServingEngine

    rng = np.random.default_rng(11)
    perm = rng.permutation(corpus_n)            # skewed placement
    order = rng.permutation(corpus_n)           # request order

    images, captions, _ = make_corpus(corpus_n, res=32, seed=0)
    embedder = ProxyClipEmbedder(render_caption)
    img_vecs = embedder.embed_image(images)
    txt_vecs = embedder.embed_text(captions)
    embedder.set_corpus_anchor(img_vecs)
    prompts = [captions[i] for i in order]

    def build(routing):
        blob = BlobStore()
        payloads = np.array([blob.put(im) for im in images], np.int64)
        dbs = [VectorDB(embedder.dim, corpus_n, name=f"node{i}")
               for i in range(n_nodes)]
        for node in range(n_nodes):
            idxs = perm[node::n_nodes]
            dbs[node].add(img_vecs[idxs], txt_vecs[idxs], payloads[idxs],
                          t=0.0)
        return CacheGenius(embedder=embedder, dbs=dbs, blob_store=blob,
                           backend=NullBackend(32), routing=routing)

    out: Dict = {"n_requests": corpus_n, "n_nodes": n_nodes,
                 "max_batch": max_batch}
    gains = []
    for rate in C.ARRIVAL_RATES:
        hit = {}
        for routing in ("score", "centroid"):
            system = build(routing)
            engine = ServingEngine(system, max_batch=max_batch)
            done = engine.run(poisson_arrivals(prompts, rate, seed=13))
            assert len(done) == len(prompts)
            qd = np.array([c.queue_delay for c in done])
            lat = np.array(system.stats.latencies)
            tag = f"{routing}_rate{rate:g}"
            out[f"hit_rate_{tag}"] = system.stats.hit_rate
            out[f"qd_p50_{tag}"] = float(np.percentile(qd, 50))
            out[f"qd_p95_{tag}"] = float(np.percentile(qd, 95))
            out[f"latency_{tag}"] = float(lat.mean())
            hit[routing] = system.stats.hit_rate
        gains.append(hit["score"] - hit["centroid"])
    out["hit_rate_gain_mean"] = float(np.mean(gains))
    # the acceptance gate: score routing >= centroid at every load, and
    # strictly better somewhere
    out["score_beats_centroid_hitrate"] = bool(
        all(g >= 0.0 for g in gains) and max(gains) > 0.0)
    return out


# ---------------------------------------------------------------------------
# Fig. 19 — LCU vs LRU/LFU/FIFO hit rate across cache updates
# ---------------------------------------------------------------------------


def _fig19_trace(n: int, seed: int = 31):
    """The workload where semantic eviction matters (the paper's LCU
    premise): a semantically TIGHT popular cluster whose active subset
    rotates (popular items 'rest' then return — recency/frequency evict
    them while resting), plus a stream of one-off novel prompts (semantic
    outliers that age-based policies keep while they push capacity)."""
    from repro.data.synthetic import all_specs, caption_of
    rng = np.random.default_rng(seed)
    pool = [s for s in all_specs() if s.shape in ("circle", "ring")
            and s.background == "black"][:60]
    rng.shuffle(pool)
    noise_pool = [s for s in all_specs() if s.background != "black"]
    prompts = []
    for i in range(n):
        window = i * 5 // n                    # 5 rotation phases
        if rng.random() < 0.7:
            active = pool[(window * 12) % 60:][:30] or pool[:30]
            prompts.append(caption_of(active[rng.integers(len(active))]))
        else:
            prompts.append(caption_of(
                noise_pool[rng.integers(len(noise_pool))]))
    return prompts


def fig19_lcu(n_requests: int = 400, updates: int = 5) -> Dict:
    stack = C.get_stack()
    prompts = _fig19_trace(n_requests)
    rows = {}
    for policy in sorted(POLICIES):
        from repro.launch.serve import build_system
        system, _, _, _ = build_system(
            n_nodes=4, corpus_n=len(stack.corpus_images),
            capacity_per_node=60, eviction=policy,
            backend=stack.backend())
        system.cache_capacity = 120           # tight: eviction is binding
        system.maintenance_interval = n_requests // updates
        hit_curve = []
        window_hits = 0
        window_n = 0
        for i, p in enumerate(prompts):
            res = system.serve(p, seed=i)
            window_n += 1
            if res.route is not Route.TXT2IMG or res.fast_path:
                window_hits += 1
            if (i + 1) % (n_requests // updates) == 0:
                hit_curve.append(window_hits / max(window_n, 1))
                window_hits = window_n = 0
        rows[policy] = {"hit_rate_after_updates": hit_curve,
                        "final": hit_curve[-1] if hit_curve else 0.0,
                        "mean_after_first_update":
                            float(np.mean(hit_curve[1:])) if len(hit_curve) > 1
                            else 0.0}
    lcu = rows["LCU"]["mean_after_first_update"]
    others = [rows[p]["mean_after_first_update"] for p in rows if p != "LCU"]
    return {"rows": rows, "lcu_beats_all": bool(lcu >= max(others))}


# ---------------------------------------------------------------------------
# Table IV — reference-image correctness
# ---------------------------------------------------------------------------


def table4_reference(n_requests: int = 80) -> Dict:
    stack = C.get_stack()
    rng = np.random.default_rng(37)
    backend = stack.backend()
    pol = GenerationPolicy()
    reqs = C.trace_prompts(n_requests, seed=41)
    corpus_vecs = stack.embedder.embed_image(stack.corpus_images)

    def run(mode):
        imgs = []
        for i, prompt in enumerate(reqs):
            q = stack.embedder.embed_text([prompt])[0]
            if mode == "correct":
                j = int(np.argmax(corpus_vecs @ q))
            elif mode == "random":
                j = int(rng.integers(0, len(corpus_vecs)))
            else:   # wrong: hard negative — least similar
                j = int(np.argmin(corpus_vecs @ q))
            ref = stack.corpus_images[j]
            imgs.append(backend.img2img(prompt, ref, pol.steps_ref, seed=i))
        imgs = np.stack(imgs)
        return {"clip_score": C.clip_score(stack.scorer, reqs, imgs),
                "pick_score": C.pick_score(stack.scorer, reqs, imgs)}

    rows = {m: run(m) for m in ("wrong", "random", "correct")}
    rows["ordering_ok"] = bool(
        rows["correct"]["clip_score"] > rows["random"]["clip_score"]
        > rows["wrong"]["clip_score"] - 1e-9)
    return rows


# ---------------------------------------------------------------------------
# Table V — embedding-model choice
# ---------------------------------------------------------------------------


def table5_embeddings(n_requests: int = 100) -> Dict:
    from repro.core.embeddings import BertProxyEmbedder
    stack = C.get_stack()
    reqs = C.trace_prompts(n_requests, seed=43)
    backend = stack.backend()
    pol = GenerationPolicy()
    corpus_img_vecs_clip = stack.embedder.embed_image(stack.corpus_images)
    bert = BertProxyEmbedder()
    bert_img = BertProxyEmbedder(image_encoder=stack.embedder)

    def run(text_emb, img_vecs):
        imgs = []
        for i, prompt in enumerate(reqs):
            q = text_emb.embed_text([prompt])[0]
            j = int(np.argmax(img_vecs @ q))
            ref = stack.corpus_images[j]
            imgs.append(backend.img2img(prompt, ref, pol.steps_ref, seed=i))
        imgs = np.stack(imgs)
        return {"clip_score": C.clip_score(stack.scorer, reqs, imgs),
                "pick_score": C.pick_score(stack.scorer, reqs, imgs)}

    rows = {
        "bert_only": run(bert, bert.embed_image(stack.corpus_images)),
        "bert_text_clip_image": run(bert_img, corpus_img_vecs_clip),
        "clip_clip": run(stack.embedder, corpus_img_vecs_clip),
    }
    rows["ordering_ok"] = bool(
        rows["clip_clip"]["clip_score"]
        >= rows["bert_text_clip_image"]["clip_score"]
        >= rows["bert_only"]["clip_score"] - 1e-9)
    return rows


# ---------------------------------------------------------------------------
# latent-depth cache — resume denoising from archived intermediates
# ---------------------------------------------------------------------------


def latent_depth_cache(n_requests: int = 120, corpus_n: int = 32,
                       n_nodes: int = 2) -> Dict:
    """Finished-image-only caching vs the latent-depth cache on the
    band-mutation workload, at each target hit-rate in ``C.HIT_RATES``.

    Both arms replay the IDENTICAL trace on identically built fleets with
    ample capacity, so routes and hit-rate match exactly; the only degree
    of freedom is whether an img2img-band match near an archived
    generation resumes from a noised intermediate (depth k: only the
    remaining K - k steps run) or re-runs the full K-step SDEdit chain.
    The acceptance claim is ``steps_below_baseline_everywhere``: mean
    denoising steps per request strictly below the baseline at equal
    hit-rate, for every swept rate.

    Stack-free: NullBackend + proxy embedder (depth-0 parity with the
    real DiffusionBackend is pinned by tests/test_latent_depth.py), so CI
    can smoke it without training the diffusion stack."""
    from repro.core.trace import band_mutation_trace
    from repro.launch.serve import build_system

    out: Dict = {"n_requests": n_requests, "corpus_n": corpus_n,
                 "n_nodes": n_nodes}
    ok = True
    for rate in C.HIT_RATES:
        reqs = band_mutation_trace(n_requests, band_fraction=rate, seed=0)
        arms = {}
        for tag, depths in (("base", None), ("latent", True)):
            system, _, _, _ = build_system(
                n_nodes=n_nodes, corpus_n=corpus_n,
                capacity_per_node=20 * n_requests, seed=0,
                latent_depths=depths)
            for i, r in enumerate(reqs):
                system.serve(r.prompt, seed=i)
            st = system.stats
            lat = np.array(st.latencies)
            arms[tag] = st
            key = f"{tag}_rate{rate:g}"
            out[f"hit_rate_{key}"] = st.hit_rate
            out[f"mean_steps_{key}"] = st.mean_steps
            out[f"lat_p50_{key}"] = float(np.percentile(lat, 50))
            out[f"lat_p95_{key}"] = float(np.percentile(lat, 95))
        out[f"latent_resumes_rate{rate:g}"] = arms["latent"].latent_resumes
        ok &= (arms["latent"].hit_rate == arms["base"].hit_rate
               and arms["latent"].route_counts == arms["base"].route_counts
               and arms["latent"].latent_resumes > 0
               and arms["latent"].mean_steps < arms["base"].mean_steps)
    out["steps_below_baseline_everywhere"] = bool(ok)
    return out


def frontdoor_load(corpus_n: int = 80, n_nodes: int = 2,
                   max_batch: int = 8, n_premium: int = 48,
                   quota_rate: float = 20.0, quota_burst: int = 8) -> Dict:
    """Multi-tenant front-door gateway under load: per-tier queue-delay
    percentiles, quota rejection rate, Jain's fairness index, and the two
    acceptance gates — TIER ISOLATION (a batch tenant offered 5× its
    token-bucket quota moves premium p95 queue delay by < 20% of the
    uncontended run, small absolute floor for CI jitter) and THROUGHPUT
    (the gateway path serves a merged trace within 10% of a direct
    ``ServingEngine.run``).

    Three phases: (1) paced multi-tenant traffic at each
    ``C.ARRIVAL_RATES`` wall rate, tiers from ``C.TIER_NAMES`` cycled
    across ``max(C.TENANT_COUNTS)`` tenants; (2) the isolation A/B —
    premium burst alone vs premium burst + ``t-1`` batch tenants flooding
    5× quota, for each ``t`` in ``C.TENANT_COUNTS``; (3) the throughput
    ratio.  Stack-free: NullBackend + proxy embedder (the gateway is
    pure orchestration; pixels come from the render stand-in)."""
    from repro.core.trace import merge_arrivals, poisson_arrivals
    from repro.frontdoor import BackpressureError, Gateway
    from repro.launch.frontdoor import jain_fairness
    from repro.launch.serve import build_system
    from repro.runtime.serving import Request, ServingEngine

    trace = RequestTrace(seed=5, n_specs=800)
    prompts = [r.prompt for r in trace.generate(600)]

    def fresh_engine() -> ServingEngine:
        system, _, _, _ = build_system(n_nodes=n_nodes, corpus_n=corpus_n,
                                       capacity_per_node=corpus_n + 400,
                                       seed=0)
        engine = ServingEngine(system, max_batch=max_batch)
        # absorb compile/trace cost before anything is timed
        engine.serve_group([Request(prompts[i], i)
                            for i in range(max_batch)])
        return engine

    def qd(handles, pct):
        return float(np.percentile([h.meta["queue_delay"]
                                    for h in handles], pct))

    out: Dict = {"n_nodes": n_nodes, "max_batch": max_batch,
                 "quota_rate": quota_rate, "quota_burst": quota_burst}

    # -- phase 1: paced multi-tenant traffic per offered wall rate ----------
    n_tenants = max(C.TENANT_COUNTS)
    tiers = [C.TIER_NAMES[i % len(C.TIER_NAMES)] for i in range(n_tenants)]
    n_paced = 36
    for rate in C.ARRIVAL_RATES:
        per = [poisson_arrivals(prompts[100 + t * n_paced:]
                                [:n_paced // n_tenants],
                                rate / n_tenants, seed=31 + t,
                                seed_base=t * n_paced,
                                tenant=f"tenant{t}", tier=tiers[t])
               for t in range(n_tenants)]
        merged = merge_arrivals(*per)
        with Gateway(fresh_engine()) as gw:
            t0 = time.perf_counter()
            handles = []
            for r in merged:
                time.sleep(max(0.0, t0 + r.arrival_time
                               - time.perf_counter()))
                handles.append(gw.submit(r.prompt, tenant=r.tenant,
                                         tier=r.tier, seed=r.seed))
            for h in handles:
                h.wait(timeout=120)
        by_tier: Dict[str, List] = {}
        for h in handles:
            by_tier.setdefault(h.meta["tier"], []).append(h)
        for tier, hs in sorted(by_tier.items()):
            out[f"qd_p50_{tier}_rate{rate:g}"] = qd(hs, 50)
            out[f"qd_p95_{tier}_rate{rate:g}"] = qd(hs, 95)
        done_per_tenant = [sum(1 for h in handles
                               if h.meta["tenant"] == f"tenant{t}")
                           for t in range(n_tenants)]
        out[f"jain_rate{rate:g}"] = jain_fairness(done_per_tenant)

    # -- phase 2: tier isolation (batch tier offered 5x its quota) ----------
    def premium_burst(gw):
        handles = [gw.submit(prompts[300 + i], tenant="prem",
                             tier="premium", seed=300 + i)
                   for i in range(n_premium)]
        for h in handles:
            h.wait(timeout=120)
        return handles

    with Gateway(fresh_engine()) as gw:
        base = premium_burst(gw)
    p95_uncontended = qd(base, 95)
    out["premium_qd_p95_uncontended"] = p95_uncontended

    isolation_ok = True
    for t in C.TENANT_COUNTS:
        n_flood = max(t - 1, 1)
        quotas = {f"batch{b}": (quota_rate, float(quota_burst))
                  for b in range(n_flood)}
        gw = Gateway(fresh_engine(), quotas=quotas)
        # flood first, THEN premium: strict tier priority must still put
        # every premium job ahead of the whole accepted batch backlog
        offered = 5 * quota_burst
        rejected = 0
        for b in range(n_flood):
            for i in range(offered):
                try:
                    gw.submit(prompts[400 + b * offered + i],
                              tenant=f"batch{b}", tier="batch",
                              seed=400 + b * offered + i)
                except BackpressureError:
                    rejected += 1
        with gw:
            contended = premium_burst(gw)
        p95 = qd(contended, 95)
        st = gw.stats()
        out[f"premium_qd_p95_contended_t{t}"] = p95
        out[f"batch_rejection_rate_t{t}"] = rejected / (n_flood * offered)
        accepted_per_flood = [st["accepted_by_tenant"].get(f"batch{b}", 0)
                              for b in range(n_flood)]
        out[f"jain_batch_accept_t{t}"] = jain_fairness(accepted_per_flood)
        isolation_ok &= p95 <= max(1.2 * p95_uncontended,
                                   p95_uncontended + 0.05)
    out["tier_isolation_ok"] = bool(isolation_ok)

    # -- phase 3: gateway throughput vs direct ServingEngine.run ------------
    n_tp = 96
    half = n_tp // 2
    merged = merge_arrivals(
        poisson_arrivals(prompts[200:200 + half], 1e9, seed=7,
                         tenant="a", tier="standard"),
        poisson_arrivals(prompts[200 + half:200 + n_tp], 1e9, seed=8,
                         seed_base=half, tenant="b", tier="standard"))
    direct_rps = gateway_rps = 0.0
    for _ in range(2):                       # best-of-2 absorbs OS jitter
        direct = fresh_engine()
        t0 = time.perf_counter()
        done = direct.run(merged)
        direct_rps = max(direct_rps,
                         len(done) / (time.perf_counter() - t0))

        gw = Gateway(fresh_engine(), max_depth=2 * n_tp, fair=False)
        handles = [gw.submit(r.prompt, tenant=r.tenant, tier=r.tier,
                             seed=r.seed) for r in merged]
        t0 = time.perf_counter()
        with gw:
            for h in handles:
                h.wait(timeout=240)
        gateway_rps = max(gateway_rps,
                          len(handles) / (time.perf_counter() - t0))
    out["direct_rps"] = direct_rps
    out["gateway_rps"] = gateway_rps
    out["throughput_ratio"] = gateway_rps / max(direct_rps, 1e-9)
    out["throughput_ok"] = bool(out["throughput_ratio"] >= 0.9)
    return out


def fault_recovery(n_requests: int = 160, corpus_n: int = 120,
                   n_nodes: int = 3) -> Dict:
    """Crash-restart economics: journaled rejoin vs cold rejoin.

    Two identically built fleets replay the IDENTICAL Zipf trace.  At
    ``C.CRASH_AT`` of the trace the busiest node hard-crashes
    (``CacheGenius.crash_node``: cache lost, nothing reassigned) and
    immediately rejoins — from its ``CacheJournal`` replay in one arm,
    cold in the other.  The journaled arm must restore the victim's
    VectorDB bitwise (every ``snapshot()`` array) and, on the post-crash
    half of the trace, beat the cold arm's cache-match hit rate (the
    ``journaled_beats_cold_hit_rate`` gate — history fast-path hits are
    excluded because they serve from the shared blob store and survive
    either way).  A final phase corrupts ``C.CORRUPT_FRAC`` of the blob
    store and replays hot prompts: every corrupted hit must degrade to
    the full-generation miss path with zero failed serves.

    Also reports journal-replay wall time against cache size (the
    restart-latency scaling a deployment actually budgets for).

    Stack-free: NullBackend + proxy embedder, same as latent_depth_cache."""
    import shutil
    import tempfile

    from repro.faults import attach_journals
    from repro.launch.serve import build_system

    cut = min(n_requests - 1, max(1, int(n_requests * C.CRASH_AT)))
    reqs = list(RequestTrace(seed=3).generate(n_requests))
    out: Dict = {"n_requests": n_requests, "corpus_n": corpus_n,
                 "n_nodes": n_nodes, "crash_at": C.CRASH_AT,
                 "corrupt_frac": C.CORRUPT_FRAC}

    def _db_hits(st):
        rc = st.route_counts
        return rc.get("hit_return", 0) + rc.get("img2img", 0)

    arms: Dict[str, Dict] = {}
    roots = []
    for tag in ("journaled", "cold"):
        system, _, _, _ = build_system(
            n_nodes=n_nodes, corpus_n=corpus_n,
            capacity_per_node=4 * corpus_n, seed=0)
        journals = None
        if tag == "journaled":
            root = tempfile.mkdtemp(prefix="fault_recovery_")
            roots.append(root)
            journals = attach_journals(system, root, snapshot_every=32)
        for i, r in enumerate(reqs[:cut]):
            system.serve(r.prompt, seed=i)
        victim = max(range(n_nodes), key=lambda n: system.dbs[n].size)
        pre = system.dbs[victim].size
        old = system.crash_node(victim)
        t0 = time.perf_counter()
        if journals is not None:
            j = journals[victim]
            db = j.replay(old.dim, old.capacity, name=old.name,
                          use_pallas=old.use_pallas,
                          interpret=old.interpret)
            db.attach_journal(j)
            system.rejoin_node(victim, db)
            live, rest = old.snapshot(), db.snapshot()
            out["bitwise_restore_ok"] = bool(
                set(live) == set(rest)
                and all(np.array_equal(live[k], rest[k]) for k in live))
        else:
            system.rejoin_node(victim)
        recovery_s = time.perf_counter() - t0
        restored = system.dbs[victim].size
        hits0, req0 = _db_hits(system.stats), system.stats.requests
        for i, r in enumerate(reqs[cut:]):
            system.serve(r.prompt, seed=cut + i)
        post_n = system.stats.requests - req0
        arms[tag] = {
            "victim": victim, "pre_crash_entries": pre,
            "restored_entries": restored if tag == "journaled" else None,
            "recovery_s": recovery_s,
            "post_hit_rate": (_db_hits(system.stats) - hits0)
            / max(post_n, 1),
            "system": system,
        }
        out[f"recovery_s_{tag}"] = recovery_s
        out[f"post_crash_hit_rate_{tag}"] = arms[tag]["post_hit_rate"]
    out["victim_node"] = arms["journaled"]["victim"]
    out["victim_entries"] = arms["journaled"]["pre_crash_entries"]
    out["restored_entries"] = arms["journaled"]["restored_entries"]
    out["journaled_beats_cold_hit_rate"] = bool(
        arms["journaled"]["post_hit_rate"]
        > arms["cold"]["post_hit_rate"])

    # -- degraded-mode phase: corrupt a fraction of the blob store and
    # replay the hottest prompts — corrupted hits must degrade to the
    # full miss path, never fail
    system = arms["journaled"]["system"]
    store = system.blob_store
    rng = np.random.default_rng(11)
    bids = sorted(store._blobs)
    k = max(1, int(round(len(bids) * C.CORRUPT_FRAC)))
    for bid in rng.choice(np.asarray(bids), size=k, replace=False):
        store.corrupt(int(bid), rng)
    ch0, dg0 = system.stats.corrupt_hits, system.stats.degraded_serves
    t0 = time.perf_counter()
    served = 0
    for i, r in enumerate(reqs[:cut]):
        res = system.serve(r.prompt, seed=n_requests + i)
        served += res.image is not None
    out["degraded_rps"] = served / max(time.perf_counter() - t0, 1e-9)
    out["corrupt_hits"] = system.stats.corrupt_hits - ch0
    out["degraded_serves"] = system.stats.degraded_serves - dg0
    out["degraded_zero_failures"] = bool(served == cut)

    # -- restart-latency scaling: journal-replay wall vs cache size
    for frac, label in ((0.5, "half"), (1.0, "full")):
        cn = max(8, int(corpus_n * frac))
        system, _, _, _ = build_system(
            n_nodes=n_nodes, corpus_n=cn, capacity_per_node=4 * corpus_n,
            seed=0)
        root = tempfile.mkdtemp(prefix="fault_recovery_scale_")
        roots.append(root)
        journals = attach_journals(system, root, snapshot_every=32)
        victim = max(range(n_nodes), key=lambda n: system.dbs[n].size)
        old = system.crash_node(victim)
        t0 = time.perf_counter()
        db = journals[victim].replay(
            old.dim, old.capacity, name=old.name,
            use_pallas=old.use_pallas, interpret=old.interpret)
        out[f"replay_s_{label}_cache"] = time.perf_counter() - t0
        out[f"replay_entries_{label}_cache"] = int(db.size)
    for root in roots:
        shutil.rmtree(root, ignore_errors=True)
    arms["journaled"].pop("system")
    arms["cold"].pop("system")
    return out


ALL_BENCHMARKS = {
    "fig1_psnr_steps": fig1_psnr_steps,
    "table1_quality": table1_quality,
    "table2_latency": table2_latency,
    "fig12_cdf": fig12_cdf,
    "fig14_scheduler": fig14_scheduler,
    "fig15_threshold": fig15_threshold,
    "fig16_steps": fig16_steps,
    "table3_prompt_opt": table3_prompt_opt,
    "fig17_cost": fig17_cost,
    "fig18_throughput": fig18_throughput,
    "serving_batch_throughput": serving_batch_throughput,
    "serving_latency_curve": serving_latency_curve,
    "retrieval_scan": retrieval_scan,
    "scheduling_quality": scheduling_quality,
    "latent_depth_cache": latent_depth_cache,
    "frontdoor_load": frontdoor_load,
    "fault_recovery": fault_recovery,
    "fig19_lcu": fig19_lcu,
    "table4_reference": table4_reference,
    "table5_embeddings": table5_embeddings,
}

# Benchmarks that never touch the trained diffusion stack — the driver
# skips the (slow) stack build when only these are selected.
STACK_FREE = {"retrieval_scan", "scheduling_quality", "latent_depth_cache",
              "frontdoor_load", "fault_recovery"}
