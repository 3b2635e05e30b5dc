#!/usr/bin/env python3
"""Chip smoke: drive the CacheGenius serving path once on a TPU.

    python chip_smoke.py               # one chip: DiT-B/2 served end to end
    python chip_smoke.py --four-chips  # four chips: mesh-sharded retrieval

The one-chip run builds a 4-node fleet over a 600-image corpus at 256 px
with the Pallas retrieval scans compiled, puts a DiT-B/2 (12 layers,
d_model 768, patch 2, f8 VAE, random weights from ``SEED``) behind it,
precompiles the step-level buckets for 8 slots, and serves a Poisson
trace through ``ServingEngine.run(step_level=True)``.  It then checks:

* every request returns a finite 256x256x3 image, and the route mix
  holds txt2img and at least one cache hit or img2img;
* one Pallas ``search_cluster_nodes`` call on the live slabs against a
  float64 numpy top-k (ids equal apart from near-ties, scores within
  ``SCAN_TOL``);
* one txt2img chain through the slot engine against ``txt2img_batch``
  for the same prompt and seed at HIGHEST matmul precision (max abs
  difference within ``SLOT_TOL``); the served image's difference at the
  default precision is printed.

``--four-chips`` runs only the sharded phase: ``ClusterIndex`` over 8
nodes x 4096 rows x 512 dims with Pallas scans on a 4-device node mesh
against the same index on one device, before and after incremental row
updates, plus a short step-level serve at mesh 4 against mesh 1 on one
trace.  Scan ids and routes must be equal.

Earlier lines print the device, compile seconds, route mix and wall
times (smoke figures, not benchmark metrics).  The last line is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check
passed.  Without a TPU the script exits non-zero before doing any work.
The compile cache is the one ``repro.launch.mesh.enable_compile_cache``
selects.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# weights, corpus, trace and vectors are all made from this seed
SEED = 0
# |Pallas score - float64 score| for one scan over unit vectors of 512
# dims: float32 accumulation error is ~1e-6, a bf16 single-pass matmul
# would be ~1e-3
SCAN_TOL = 1e-4
# max |slot-engine image - txt2img_batch image| at HIGHEST matmul
# precision: two XLA programs (8-slot ragged step, batch-1 scan) whose
# float32 rounding differs, compounded over 30 DDIM steps
SLOT_TOL = 1e-3


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    """The first device, which must be a TPU: there is no CPU fallback."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX's first device is "
                         f"{dev.platform!r}); this smoke runs only on a TPU")
    return dev


def dit_b2_config(img_res: int = 256):
    """DiT-B/2 at its published width over the f8 VAE."""
    from repro.configs.registry import get_arch
    from repro.configs.shapes import ShapeCell
    return get_arch("dit-b2").make_config(
        ShapeCell("serve_256", "gen", img_res=img_res))


def make_backend(dcfg, embedder, seed: int):
    """``DiffusionBackend`` with random weights made from ``seed``.

    DiT's adaLN-zero init leaves the modulation and output projections at
    zero, which makes every block the identity and eps exactly 0; those
    leaves get small random values too, so the backbone shapes the
    result the checks compare."""
    import jax
    import jax.numpy as jnp

    from repro.models.diffusion import dit as dit_mod
    from repro.models.diffusion import vae as vae_mod
    from repro.runtime.serving import DiffusionBackend

    net = jax.jit(dit_mod.init_dit, static_argnums=1)(
        jax.random.key(seed), dcfg.net)
    leaves, tree = jax.tree_util.tree_flatten(net)
    keys = jax.random.split(jax.random.key(seed + 2), len(leaves))
    net = jax.tree_util.tree_unflatten(tree, [
        0.02 * jax.random.normal(k, x.shape, x.dtype)
        if not bool(jnp.any(x)) else x for k, x in zip(keys, leaves)])
    vae = jax.jit(vae_mod.init_vae, static_argnums=1)(
        jax.random.key(seed + 1), dcfg.vae)
    return DiffusionBackend(net, dcfg.net, vae, dcfg.vae,
                            embed_prompt=lambda p: embedder.embed_text([p])[0])


def warm_scans(system, buckets) -> float:
    """Compile the retrieval scans for every query bucket the serve can
    issue; returns seconds."""
    t0 = time.perf_counter()
    ci = system.cluster_index
    for b in buckets:
        q = np.ones((b, ci.dim), np.float32)
        ci.search_cluster_nodes(q, system.topk)
        ci.search_batch(q, [0] * b, system.topk, count_queries=False)
    return time.perf_counter() - t0


def check_scan_vs_float64(system, embedder, prompts, k: int) -> float:
    """One Pallas ``search_cluster_nodes`` on the live slabs against a
    float64 numpy top-k with the same union semantics.  Scores compare
    by position (both lists are sorted descending); where the ids differ
    (a near-tie), the returned id must reach its returned score in one
    index plane.  Returns the max score difference."""
    from repro.core.vdb import _union_topk
    from repro.utils import l2n

    ci = system.cluster_index
    queries = embedder.embed_text(prompts)
    got = ci.search_cluster_nodes(queries, k)
    slabs, valid = ci.device_state()
    slabs = slabs.astype(np.float64)
    q64 = l2n(np.asarray(queries, np.float32)).astype(np.float64)
    worst = 0.0
    for qi, q in enumerate(q64):
        for node in range(ci.n_nodes):
            planes = slabs[:, node] @ q                      # (2, capacity)
            planes[:, ~valid[node]] = -np.inf
            order = [np.lexsort((np.arange(p.size), -p))[:k] for p in planes]
            want_s, want_i = _union_topk(
                [p[o] for p, o in zip(planes, order)], order)
            got_s, got_i = got[qi][node]
            where = f"scan q{qi} node{node}"
            check(len(got_i) == len(want_i),
                  f"{where}: {len(got_i)} ids, want {len(want_i)}")
            for gs, gi, ws, wi in zip(got_s, got_i, want_s, want_i):
                check(abs(float(gs) - ws) <= SCAN_TOL,
                      f"{where}: score {gs} (id {gi}) vs {ws} (id {wi})")
                check(gi == wi or np.min(np.abs(planes[:, gi] - gs))
                      <= SCAN_TOL,
                      f"{where}: id {gi} does not score {gs} "
                      f"({planes[:, gi]})")
                worst = max(worst, abs(float(gs) - ws))
    return worst


def check_slot_vs_batch(backend, prompt: str, steps: int, seed: int,
                        slots: int) -> float:
    """One txt2img chain through a ``slots``-slot engine against
    ``txt2img_batch`` for the same prompt and seed, both compiled at
    HIGHEST matmul precision (at the TPU default, one bf16 pass, the two
    programs round differently and 30 steps compound it).  Returns the
    max abs difference."""
    import jax

    from repro.core.pipeline import Plan
    from repro.runtime.serving import DiffusionBackend

    with jax.default_matmul_precision("highest"):
        exact = DiffusionBackend(
            backend.net_params, backend.net_cfg, backend.vae_params,
            backend.vae_cfg, backend.embed_prompt, schedule=backend.sched,
            latent_scale=backend.latent_scale,
            img2img_strength=backend.strength)
        engine = exact.make_slot_engine(slots)
        state = types.SimpleNamespace(plan=Plan(kind="gen", steps=steps),
                                      prompt=prompt, seed=seed, image=None)
        engine.admit(state, 0)
        while engine.active_count():
            engine.step()
        want = exact.txt2img_batch([prompt], steps, [seed])[0]
    diff = float(np.max(np.abs(np.asarray(state.image) - want)))
    check(diff <= SLOT_TOL, f"slot image differs by {diff} > {SLOT_TOL}")
    return diff


def serve_phase(dcfg, *, n_nodes: int, corpus_n: int, capacity: int,
                slots: int, n_requests: int, rate: float, seed: int) -> None:
    """The one-chip phase: build, precompile, serve, check."""
    from repro.core.embeddings import ProxyClipEmbedder
    from repro.core.policy import GenerationPolicy
    from repro.core.trace import RequestTrace, poisson_arrivals
    from repro.data.synthetic import render_caption
    from repro.launch.serve import build_system
    from repro.runtime.serving import ServingEngine

    t0 = time.perf_counter()
    embedder = ProxyClipEmbedder(render_caption)
    backend = make_backend(dcfg, embedder, seed)
    system, embedder, _, _ = build_system(
        n_nodes=n_nodes, corpus_n=corpus_n, capacity_per_node=capacity,
        policy=GenerationPolicy(), backend=backend, seed=seed,
        use_pallas=True)
    check(system.cluster_index.use_pallas, "cluster index is not on Pallas")
    res = backend.image_res
    print(f"build            : {time.perf_counter() - t0:.2f}s  "
          f"({n_nodes} nodes, corpus {corpus_n} at {res}px, "
          f"capacity {capacity})", flush=True)

    backend.precompile_step_level(slots)
    buckets = [b for b in (1, 2, 4, 8, 16) if b <= slots]
    scan_s = warm_scans(system, buckets)
    for key, secs in backend.compile_seconds.items():
        print(f"compile          : {key[0]}@{key[2]} {secs:.2f}s", flush=True)
    print(f"compile          : retrieval scans q{buckets} {scan_s:.2f}s",
          flush=True)

    reqs = list(RequestTrace(seed=seed + 1).generate(n_requests))
    arrivals = poisson_arrivals(reqs, rate, seed=seed + 1)
    engine = ServingEngine(system, max_batch=slots)
    t0 = time.perf_counter()
    done = engine.run(arrivals, step_level=True, slot_capacity=slots)
    serve_s = time.perf_counter() - t0
    check(len(done) == n_requests, f"{len(done)} of {n_requests} completed")
    for c in done:
        img = np.asarray(c.result.image)
        check(img.shape == (res, res, 3),
              f"image shape {img.shape} != {(res, res, 3)}")
        check(bool(np.isfinite(img).all()), "non-finite image")
    mix = dict(system.stats.route_counts)
    check(mix.get("txt2img", 0) > 0, f"no txt2img in route mix {mix}")
    check(mix.get("hit_return", 0) + mix.get("img2img", 0) > 0,
          f"no hit or img2img in route mix {mix}")
    print(f"serve            : {n_requests} requests in {serve_s:.2f}s wall, "
          f"{engine.last_slot_engine.step_calls} step launches", flush=True)
    print(f"route mix        : {mix}", flush=True)

    scan_diff = check_scan_vs_float64(system, embedder,
                                      [r.prompt for r in reqs[:8]],
                                      system.topk)
    print(f"scan vs float64  : max |score diff| {scan_diff:.3g} "
          f"(tol {SCAN_TOL:g})", flush=True)

    gen = next(c for c in done if c.result.route.value == "txt2img"
               and c.result.fast_path is None)
    prompt = (system.prompt_optimizer.optimize(gen.request.prompt)
              if system.use_prompt_optimizer else gen.request.prompt)
    steps, gen_seed = gen.result.steps, gen.request.seed
    served = float(np.max(np.abs(
        np.asarray(gen.result.image)
        - backend.txt2img_batch([prompt], steps, [gen_seed])[0])))
    diff = check_slot_vs_batch(backend, prompt, steps, gen_seed, slots)
    print(f"slot vs batch    : max |diff| {diff:.3g} at HIGHEST precision "
          f"(tol {SLOT_TOL:g}); served image vs txt2img_batch at default "
          f"precision {served:.3g}", flush=True)


def _unit_rows(rng, n: int, dim: int) -> np.ndarray:
    v = rng.normal(size=(n, dim)).astype(np.float32)
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


def _same_rows(a, b) -> bool:
    return len(a) == len(b) and all(
        np.array_equal(ai, bi) for (_, ai), (_, bi) in zip(a, b))


def four_chip_phase(*, mesh: int, nodes: int, rows: int, dim: int,
                    n_requests: int, seed: int) -> None:
    """Mesh-sharded retrieval on ``mesh`` devices against one device."""
    import jax

    from repro.core.cluster_index import ClusterIndex
    from repro.core.trace import RequestTrace, poisson_arrivals
    from repro.core.vdb import VectorDB
    from repro.launch.serve import build_system
    from repro.runtime.serving import ServingEngine

    check(len(jax.devices()) >= mesh,
          f"--four-chips needs {mesh} devices, found {len(jax.devices())}")
    rng = np.random.default_rng(seed)
    dbs = [VectorDB(dim, rows, name=f"node{i}", use_pallas=True)
           for i in range(nodes)]
    for i, db in enumerate(dbs):
        db.add(_unit_rows(rng, rows, dim), _unit_rows(rng, rows, dim),
               np.arange(rows) + i * rows, t=0.0)
    t0 = time.perf_counter()
    ci1 = ClusterIndex.from_dbs(dbs, use_pallas=True, mesh_nodes=1)
    cim = ClusterIndex.from_dbs(dbs, use_pallas=True, mesh_nodes=mesh)
    shards = sorted((s.device.id, s.data.shape)
                    for s in cim._slabs.addressable_shards)
    print(f"slab shards      : {shards}", flush=True)
    check(len({d for d, _ in shards}) == mesh,
          f"slabs sit on {len(shards)} devices, want {mesh}")
    check(all(shape[1] == nodes // mesh for _, shape in shards),
          f"uneven slab shards {shards}")
    print(f"slab bytes       : {ci1.per_device_slab_bytes()} on one device, "
          f"{cim.per_device_slab_bytes()} per device at mesh {mesh}",
          flush=True)

    def compare(tag: str) -> None:
        q = _unit_rows(rng, 16, dim)
        nids = rng.integers(0, nodes, size=16)
        t = time.perf_counter()
        pairs = [
            ("per-node", ci1.search_cluster_nodes(q, 8),
             cim.search_cluster_nodes(q, 8)),
            ("masked", ci1.search_batch(q, nids, 8, count_queries=False),
             cim.search_batch(q, nids, 8, count_queries=False)),
            ("global", ci1.search_cluster(q, 8), cim.search_cluster(q, 8)),
        ]
        for mode, a, b in pairs:
            if mode == "per-node":
                same = all(_same_rows(x, y) for x, y in zip(a, b))
            else:
                same = _same_rows(a, b)
            check(same, f"{tag} {mode} scan ids differ at mesh {mesh}")
        print(f"scan parity      : {tag}: per-node/masked/global ids equal "
              f"({time.perf_counter() - t:.2f}s incl. compile)", flush=True)

    compare("built")
    for i in range(0, nodes, 3):   # incremental row updates on both
        dbs[i].add(_unit_rows(rng, 5, dim), _unit_rows(rng, 5, dim),
                   np.arange(5) + 10 ** 6 + i * 10, t=1.0)
    check(all(s.data.shape[1] == nodes // mesh
              for s in cim._slabs.addressable_shards),
          "row updates moved the slab shards")
    compare("after row updates")
    print(f"retrieval phase  : {time.perf_counter() - t0:.2f}s", flush=True)

    reqs = list(RequestTrace(seed=seed + 1).generate(n_requests))
    outs = {}
    for m in (1, mesh):
        system, _, _, _ = build_system(n_nodes=nodes, corpus_n=600,
                                       capacity_per_node=400, seed=seed,
                                       mesh_nodes=m, use_pallas=True)
        done = ServingEngine(system, max_batch=8).run(
            poisson_arrivals(reqs, 50.0, seed=seed + 1), step_level=True)
        outs[m] = [(c.result.route.value, c.result.fast_path, c.result.node)
                   for c in done]
    check(outs[1] == outs[mesh],
          f"routes differ between mesh 1 and mesh {mesh}")
    mix = {}
    for route, fast, _ in outs[mesh]:
        mix[fast or route] = mix.get(fast or route, 0) + 1
    print(f"serve parity     : {n_requests} requests, routes and nodes equal "
          f"at mesh 1 and {mesh}; mix {mix}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded retrieval phase on 4 "
                    "chips, against the same index on one")
    args = ap.parse_args()

    t_start = time.perf_counter()
    dev = require_tpu()
    import jax

    from repro.launch.mesh import enable_compile_cache
    cache = enable_compile_cache()
    print(f"device           : {dev.platform} {dev.device_kind} x"
          f"{len(jax.devices())}", flush=True)
    print(f"compile cache    : {cache}", flush=True)
    if args.four_chips:
        four_chip_phase(mesh=4, nodes=8, rows=4096, dim=512, n_requests=24,
                        seed=SEED)
    else:
        serve_phase(dit_b2_config(256), n_nodes=4, corpus_n=600,
                    capacity=400, slots=8, n_requests=32, rate=4.0,
                    seed=SEED)
    print(f"wall             : {time.perf_counter() - t_start:.2f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
