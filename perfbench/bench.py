"""One run of one cell: set-up, the measured window, and what the window
leaves behind for the metric readers and the check.

The window drives the program's own entry point: ``build_system`` with a
``DiffusionBackend``, the fleet's rows added through ``VectorDB.add``,
then ``ServingEngine.run(arrivals, step_level=True)``.  The program gets
only what this module generates from the seed: weights, corpus size,
fleet rows and traffic.

Around the calls into each layer the benchmark wraps its own spans
(``bench:<label>`` ``TraceAnnotation``s plus host wall times) and, for a
seed-drawn sample of the window's step launches, decodes and scans,
keeps what went in and what came out, for :mod:`check` to compare with
the plain reference once the window has closed.
"""
from __future__ import annotations

import gc
import shutil
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import traffic as traffic_mod

# how many of the window's calls the check compares, drawn from the seed
SAMPLE_STEPS = 8
SAMPLE_DECODES = 6
SAMPLE_SCANS = 6


def sub_seed(seed: int, *path: int) -> int:
    """A 31-bit seed for one purpose, drawn from the run's seed."""
    return int(np.random.default_rng([seed, *path]).integers(0, 2 ** 31))


class Reservoir:
    """A uniform sample of fixed size over a stream of unknown length."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng = size, rng
        self.items: List[Any] = []
        self.seen = 0

    def offer(self, make: Callable[[], Any]) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(make())
            return
        j = int(self.rng.integers(self.seen))
        if j < self.size:
            self.items[j] = make()


@dataclass
class Recorder:
    """Host spans, counters and the sampled calls of one window.

    Each request is followed by its own objects, never by its position:
    the plan kind it was admitted with and the slot-engine handle it was
    seated under are recorded against its pipeline state at admission,
    and moved onto its result when it is finalized, in whatever order
    the program finalizes."""

    rng: np.random.Generator
    walls: Dict[str, List[float]] = field(
        default_factory=lambda: defaultdict(list))
    scan_queries: List[int] = field(default_factory=list)
    plan_kinds: List[str] = field(default_factory=list)
    img_inits: int = 0
    on: bool = False
    # id(state) -> [state, admitted plan kind, slot handle or None]
    admitted: Dict[int, list] = field(default_factory=dict)
    # id(result) -> (result, admitted plan kind, slot handle or None)
    served: Dict[int, tuple] = field(default_factory=dict)

    def __post_init__(self):
        self.steps = Reservoir(SAMPLE_STEPS, self.rng)
        self.decodes = Reservoir(SAMPLE_DECODES, self.rng)
        self.scans = Reservoir(SAMPLE_SCANS, self.rng)

    def request(self, result):
        """``(plan kind at admission, slot handle)`` of the request that
        came back with ``result``, or None for one the window never
        admitted and finalized."""
        entry = self.served.get(id(result))
        if entry is None or entry[0] is not result:
            return None
        return entry[1], entry[2]


def _span(rec: Recorder, label: str, fn: Callable) -> Callable:
    from jax.profiler import TraceAnnotation

    def wrapped(*a, **k):
        t0 = time.perf_counter()
        with TraceAnnotation("bench:" + label):
            out = fn(*a, **k)
        if rec.on:
            rec.walls[label].append(time.perf_counter() - t0)
        return out
    return wrapped


def instrument(system, backend, rec: Recorder) -> None:
    """Wrap the calls into each layer with the benchmark's spans, and
    keep the sampled calls' inputs and outputs."""
    import jax

    pipe = system.pipeline
    admit = pipe.run_admission
    fin = pipe.finalize

    def run_admission(*a, **k):
        states = admit(*a, **k)
        if rec.on:
            for s in states:
                rec.plan_kinds.append(s.plan.kind)
                rec.admitted[id(s)] = [s, s.plan.kind, None]
        return states

    def finalize(system_, state):
        out = fin(system_, state)
        entry = rec.admitted.pop(id(state), None) if rec.on else None
        if entry is not None and entry[0] is state:
            rec.served[id(state.result)] = (state.result, entry[1],
                                            entry[2])
        return out
    pipe.run_admission = _span(rec, "admission", run_admission)
    pipe.finalize = _span(rec, "finalize", finalize)
    system.maintain = _span(rec, "maintain", system.maintain)

    make_engine = backend.make_slot_engine

    def make_slot_engine(capacity):
        engine = make_engine(capacity)
        seat = engine.admit

        def admit_slot(state, handle):
            seat(state, handle)
            entry = rec.admitted.get(id(state)) if rec.on else None
            if entry is not None and entry[0] is state:
                entry[2] = int(handle)
        engine.admit = admit_slot
        return engine
    backend.make_slot_engine = make_slot_engine

    ci = system.cluster_index
    search = ci.search_cluster_nodes

    def search_cluster_nodes(queries, k, **kw):
        out = search(queries, k, **kw)
        if rec.on:
            rec.scan_queries.append(len(queries))
            rec.scans.offer(lambda: (
                np.array(queries, np.float32), int(k),
                np.stack([db.valid.copy() for db in system.dbs]), out))
        return out
    ci.search_cluster_nodes = _span(rec, "scan", search_cluster_nodes)

    get = backend._get

    def get_program(kind, steps, batch):
        fn = get(kind, steps, batch)

        def call(*a):
            out = jax.block_until_ready(fn(*a))
            if not rec.on:
                return out
            # copies: the engine rewrites its host buffers in place, and a
            # CPU array may share their memory
            if kind == "step_slots":
                rec.steps.offer(lambda: (tuple(np.array(x) for x in a[1:]),
                                         np.array(out)))
            elif kind == "slot_decode":
                rec.decodes.offer(lambda: (np.array(a[1]), np.array(out)))
            elif kind == "slot_img_init":
                rec.img_inits += 1
            return out
        return _span(rec, kind, call)
    backend._get = get_program


def make_weights(cfg: dict, seed: int):
    """DiT and VAE parameters on the device, float32 as served, in one
    jitted call from the seed.  Leaves that adaLN-zero initialises to 0
    get N(0, std) values, or every block is the identity and eps is 0.

    The key is XLA's own bit generator (``rbg``): the program's init laws
    draw over 140 leaves, and the TPU compiler takes less than half the
    time over that program with it than with threefry."""
    import jax
    import jax.numpy as jnp

    from repro.models.diffusion import dit as dit_mod
    from repro.models.diffusion import vae as vae_mod

    net_cfg, vae_cfg = program_configs(cfg)
    std = float(cfg["weights"]["zero_leaf_std"])

    def init(key):
        k_net, k_fill, k_vae = jax.random.split(key, 3)
        net = dit_mod.init_dit(k_net, net_cfg)
        leaves, tree = jax.tree_util.tree_flatten(net)
        keys = jax.random.split(k_fill, len(leaves))
        net = jax.tree_util.tree_unflatten(tree, [
            jnp.where(jnp.any(x != 0), x,
                      std * jax.random.normal(k, x.shape, x.dtype))
            for k, x in zip(keys, leaves)])
        return net, vae_mod.init_vae(k_vae, vae_cfg)

    return jax.jit(init)(jax.random.key(sub_seed(seed, 1), impl="rbg"))


def program_configs(cfg: dict):
    from repro.models.diffusion.dit import DiTConfig
    from repro.models.diffusion.vae import VAEConfig
    d, v = cfg["dit"], cfg["vae"]
    net = DiTConfig(img_res=d["input_size"], in_ch=d["in_channels"],
                    patch=d["patch_size"], n_layers=d["depth"],
                    d_model=d["hidden_size"], n_heads=d["num_heads"],
                    mlp_ratio=d["mlp_ratio"], ctx_dim=d["cond_dim"])
    vae = VAEConfig(in_ch=v["in_ch"], base_ch=v["base_ch"],
                    ch_mult=tuple(v["ch_mult"]), z_ch=v["z_ch"],
                    n_res=v["n_res"])
    return net, vae


def _fleet_rows(n: int, dim: int, seed: int) -> np.ndarray:
    """(2, n, dim) float32 unit rows, made on the device in one call.
    Every node of a cell gets the same ``n``, so one compiled program
    makes them all, and one compiled row update writes them."""
    import jax
    import jax.numpy as jnp

    def make(key):
        v = jax.random.normal(key, (2, n, dim), jnp.float32)
        return v / jnp.linalg.norm(v, axis=-1, keepdims=True)
    return np.asarray(jax.jit(make)(jax.random.key(seed)))


@dataclass
class Setup:
    system: Any
    backend: Any
    weights: Any
    window: List[Any]          # the program's TimedRequests
    marks: Dict[str, float]    # seconds per set-up phase


def build(cfg: dict, spec: dict, seed: int, seconds: float, *,
          use_pallas: Optional[bool] = True) -> Setup:
    """Everything before the window: weights, the fleet, compilation of
    every shape the cell's traffic uses, and the warm-up traffic."""
    from repro.core.policy import GenerationPolicy
    from repro.core.trace import TimedRequest
    from repro.launch.serve import build_system
    from repro.models.diffusion.schedule import DiffusionSchedule
    from repro.runtime.serving import DiffusionBackend, ServingEngine

    marks: Dict[str, float] = {}
    t = time.perf_counter()
    net, vae = make_weights(cfg, seed)
    marks["weights"] = time.perf_counter() - t

    net_cfg, vae_cfg = program_configs(cfg)
    smp, pol = cfg["sampler"], cfg["policy"]
    embed = {}
    backend = DiffusionBackend(
        net, net_cfg, vae, vae_cfg,
        embed_prompt=lambda p: embed["e"].embed_text([p])[0],
        schedule=DiffusionSchedule.linear(smp["T"], smp["beta_start"],
                                          smp["beta_end"]),
        latent_scale=smp["latent_scale"],
        img2img_strength=smp["img2img_strength"])
    fleet = cfg["fleet"]
    t = time.perf_counter()
    system, embedder, _, _ = build_system(
        n_nodes=fleet["nodes"], corpus_n=spec["corpus_images"],
        capacity_per_node=fleet["rows_per_node"],
        policy=GenerationPolicy(lo=pol["lo"], hi=pol["hi"],
                                steps_full=smp["steps_full"],
                                steps_ref=smp["steps_ref"]),
        backend=backend, seed=sub_seed(spec["population_seed"], 2),
        use_pallas=use_pallas)
    embed["e"] = embedder
    if (system.topk != pol["topk"]
            or system.maintenance_interval != pol["maintenance_interval"]):
        raise RuntimeError("the program's retrieval depth or maintenance "
                           "interval differs from the configuration's")
    marks["build_system"] = time.perf_counter() - t

    t = time.perf_counter()
    # the same count on every node, leaving at least the archive room
    n = (fleet["rows_per_node"] - fleet["archive_room_per_node"]
         - max(db.size for db in system.dbs))
    for i, db in enumerate(system.dbs):
        if n <= 0:
            break
        rows = _fleet_rows(n, fleet["dim"], sub_seed(seed, 3, i))
        db.add(rows[0, :n], rows[1, :n],
               np.arange(n, dtype=np.int64) + 10 ** 12 + i * 10 ** 9, t=-1.0)
        del rows
    marks["fleet"] = time.perf_counter() - t

    t = time.perf_counter()
    slots = cfg["serving"]["slots"]
    backend.precompile_step_level(slots)
    ci = system.cluster_index
    b = 1
    while b <= cfg["serving"]["max_batch"]:
        ci.search_cluster_nodes(np.ones((b, ci.dim), np.float32),
                                system.topk)
        b *= 2
    marks["compile"] = time.perf_counter() - t

    warm, window = traffic_mod.generate(spec, seed, seconds)

    def timed(reqs):
        return [TimedRequest(r.arrival_time, r.prompt, seed=r.seed,
                             quality_tier=r.quality_tier) for r in reqs]
    t = time.perf_counter()
    ServingEngine(system, max_batch=cfg["serving"]["max_batch"]).run(
        timed(warm), step_level=True, slot_capacity=slots)
    warm_slot_paths(backend, slots, smp)
    marks["warmup"] = time.perf_counter() - t
    return Setup(system, backend, (net, vae), timed(window), marks)


def warm_slot_paths(backend, slots: int, smp: dict) -> None:
    """One txt2img and one img2img chain through a slot engine, so the
    window meets no first call of either slot-init path whatever the
    warm-up traffic routed."""
    import types

    from repro.core.pipeline import Plan

    engine = backend.make_slot_engine(slots)
    res = backend.image_res
    plans = [Plan(kind="gen", steps=smp["steps_full"]),
             Plan(kind="gen", steps=smp["steps_ref"],
                  ref=np.zeros((res, res, 3), np.float32))]
    scenes = traffic_mod.all_scenes()
    for h, plan in enumerate(plans):
        prompt = traffic_mod.caption(scenes[h])
        engine.admit(types.SimpleNamespace(plan=plan, prompt=prompt, seed=h,
                                           image=None), h)
    while engine.active_count():
        engine.step()


@dataclass
class Window:
    done: List[Any]
    wall_s: float
    slot_occupancy: List[int]
    slots: int
    progress: Dict[int, List[int]]
    compiles: int


class CompileCounter:
    """Counts programs compiled or read from the compile cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0

        def listen(name, secs, **kw):
            if name in self.EVENTS:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def serve_window(setup: Setup, cfg: dict, rec: Recorder,
                 counter: CompileCounter, *,
                 trace_dir: Optional[str] = None) -> Window:
    """The measured window: every generated request through the serving
    engine, step-level, on the engine's clock."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.runtime.serving import ServingEngine

    engine = ServingEngine(setup.system,
                           max_batch=cfg["serving"]["max_batch"])
    slots = cfg["serving"]["slots"]
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gc.collect()
    n0 = counter.n
    rec.on = True
    t0 = time.perf_counter()
    with TraceAnnotation("bench:window"):
        done = engine.run(setup.window, step_level=True, slot_capacity=slots)
    wall = time.perf_counter() - t0
    rec.on = False
    compiles = counter.n - n0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    eng = engine.last_slot_engine
    return Window(done, wall, list(engine.slot_occupancy), slots,
                  dict(getattr(eng, "progress", {})), compiles)


def host_mirror(system) -> Dict[str, np.ndarray]:
    """The fleet's host rows (the program's source of truth for the
    device slabs), kept for the scan check after the program is freed."""
    return {"img": [db.img_vecs for db in system.dbs],
            "txt": [db.txt_vecs for db in system.dbs]}
