"""Serving engine: CacheGenius front-end over a jitted diffusion backend.

This is the deployment-shaped layer: the paper's §V "asynchronous task
queue" in front of the Fig. 5 pipeline.  Three pieces:

* :class:`DiffusionBackend` — AOT-compiled txt2img / img2img samplers for a
  (tiny or full) DiT + VAE.  Every (workflow × step-count × batch-bucket)
  is compiled once up front (``precompile``), the TPU-side answer to the
  paper's Docker cold-start fix (§V: "rebuilding the image with
  preinstalled dependencies" → here: persistent compile cache + AOT).
* :class:`ServingEngine` — the request queue over the CacheGenius
  orchestrator, with TWO draining disciplines:

  - ``run(arrivals, mode="continuous")`` — **continuous batching**, the
    primary path.  An event-driven loop consumes a timestamped arrival
    process (:func:`repro.core.trace.poisson_arrivals` /
    ``trace_arrivals`` / ``bursty_arrivals``) on a virtual clock that
    advances by measured service wall time.  Whenever the in-flight step
    group (one staged-pipeline pass, i.e. one set of AOT generation
    buckets) completes, everything that has arrived in the meantime is
    admitted into the next group — up to ``max_batch`` — so a request
    never waits for a drain boundary, only for the group ahead of it.
    ``mode="drain"`` is the fixed-drain baseline at the same offered
    load: a bucket closes only when ``max_batch`` requests have arrived
    (or the trace ends), so stragglers wait out the fill time — the
    behaviour whose p95 queue delay the continuous mode beats under
    bursty traffic.  ``run(..., step_level=True)`` sharpens admission
    from step-GROUP to step granularity: a persistent slot engine
    (:class:`DiffusionSlotEngine` / :class:`EmulatedSlotEngine`)
    advances a ragged in-flight set one denoising step per compiled
    ``step_slots`` launch, admitting arrivals into free slots at ANY
    step boundary and retiring each chain the step it ends, while
    Archive/Finish run in submission order so every observable matches
    the group modes exactly.
  - ``submit`` + ``drain()`` — the legacy closed-loop surface: everything
    is queued up front and drained in FIFO micro-batches.

  Either way each ``Completed`` carries a TRUE ``queue_delay`` (time the
  request actually waited before its pipeline admission, from the
  per-stage timestamps — not submission-clock ticks) and a result with
  ``wall_total`` + per-stage ``stage_walls``.  Node failures reroute
  through ``CacheGenius.fail_node``.
* :class:`LMResponseCache` — the beyond-paper adaptation for the LM archs
  (DESIGN.md §Arch-applicability): GPTCache-style semantic response cache
  in front of decode; exact analog of Algorithm 1's HIT_RETURN branch with
  no img2img middle band (tokens are discrete).

Invariants (pinned by ``tests/test_serving_continuous.py`` and, for the
step-level mode, the ragged-admission property suite in
``tests/test_step_level.py``): on traces where batched/sequential parity
holds, continuous-mode results are a permutation (in fact,
arrival-order-identical) of fixed-drain results — batch partitioning
never changes routes, images, cache state, or hit/miss stats, and
step-level slot admission reproduces both bitwise for any slot capacity;
widely spaced single submissions reproduce sequential ``serve``
bitwise; and a run whose group sizes stay inside the precompiled buckets
triggers no JIT at serve time (step-level runs reuse exactly ONE
``step_slots`` executable per slot capacity).  The eviction sweep fires at EXACT
request-count crossings inside the Finish stage (archives past the
boundary are deferred and flushed per request), so sub-batch maintenance
intervals keep their sequential cadence — no interval clamp is needed.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import TransientBackendError
from repro.core.system import CacheGenius, GenerationBackend, Plan, \
    ServeResult
from repro.core.trace import TimedRequest
from repro.models.diffusion import dit as dit_mod
from repro.models.diffusion import vae as vae_mod
from repro.models.diffusion.sampler import (ddim_sample, ddim_timesteps,
                                            resume_noise_levels,
                                            resume_sample, sdedit_start,
                                            step_slots)
from repro.models.diffusion.schedule import DiffusionSchedule
from repro.runtime.tracing import span
from repro.utils import next_pow2


# ---------------------------------------------------------------------------
# diffusion backend (AOT-bucketed samplers)
# ---------------------------------------------------------------------------


class DiffusionBackend(GenerationBackend):
    """txt2img/img2img over a DiT+VAE with per-(kind, steps, batch) AOT
    compilation.  ``embed_prompt`` maps a prompt to the conditioning vector
    (injected; the benchmarks use the proxy CLIP embedder).

    Implements the batch-first ``GenerationBackend`` protocol directly
    (``txt2img_batch`` / ``img2img_batch`` are the required surface; the
    scalar overrides below hit the batch=1 AOT bucket without the padding
    plumbing), plus the latent-depth cache surface: ``resume_batch``
    resumes the truncated img2img DDIM chain from an archived depth-k
    latent (AOT kind ``"resume@k"``), and ``archive_latents_batch``
    produces the noised intermediates to archive (kind
    ``"latents@k1,k2,..."``) — both bucketed exactly like the classic
    kinds, so every (kind, steps, batch) compiles once."""

    supports_latent_resume = True

    def __init__(self, net_params, net_cfg: dit_mod.DiTConfig, vae_params,
                 vae_cfg: vae_mod.VAEConfig,
                 embed_prompt: Callable[[str], np.ndarray],
                 *, schedule: Optional[DiffusionSchedule] = None,
                 latent_scale: float = 1.0,
                 img2img_strength: float = 0.6):
        # one upload at construction: numpy parameters (as a pickled
        # stack yields them) would otherwise cross to the device again
        # on every launch
        self.net_params = jax.device_put(net_params)
        self.net_cfg = net_cfg
        self.vae_params = jax.device_put(vae_params)
        self.vae_cfg = vae_cfg
        self.embed_prompt = embed_prompt
        self.sched = schedule or DiffusionSchedule.linear(1000)
        self.latent_scale = latent_scale
        self.strength = img2img_strength
        self._compiled: Dict[Tuple[str, int, int], Any] = {}
        self.compile_seconds: Dict[Tuple[str, int, int], float] = {}

    @property
    def image_res(self) -> int:
        """Side of the square images this backend generates and takes as
        img2img references."""
        return self.vae_cfg.downsample * self.net_cfg.img_res

    # -- jittable cores -----------------------------------------------------
    #
    # Both cores take a VECTOR of per-request seeds: each batch element's
    # initial noise is drawn exactly as the sequential batch=1 path draws
    # it (vmap of split+normal over the element's own PRNGKey), so batching
    # requests never changes any individual request's sample trajectory.

    def _txt2img_core(self, net, vae, ctx, seeds, steps: int, batch: int):
        eps = dit_mod.make_eps_fn(net, self.net_cfg)
        el_shape = (self.net_cfg.img_res, self.net_cfg.img_res,
                    self.net_cfg.in_ch)

        def _noise(seed):
            k_noise, _ = jax.random.split(jax.random.PRNGKey(seed))
            return jax.random.normal(k_noise, (1,) + el_shape)[0]

        x_init = jax.vmap(_noise)(seeds)
        z = ddim_sample(eps, self.sched, (batch,) + el_shape, ctx,
                        jax.random.PRNGKey(0), steps=steps, x_init=x_init)
        return vae_mod.decode(vae, self.vae_cfg, z / self.latent_scale)

    def _img2img_core(self, net, vae, ref_img, ctx, seeds, steps: int):
        eps = dit_mod.make_eps_fn(net, self.net_cfg)
        mean, _ = vae_mod.encode(vae, self.vae_cfg, ref_img)
        z_ref = mean * self.latent_scale

        def _noise(seed, z1):
            k1, _ = jax.random.split(jax.random.PRNGKey(seed))
            return jax.random.normal(k1, (1,) + z1.shape)[0]

        noise = jax.vmap(_noise)(seeds, z_ref)
        x_init, t_start = sdedit_start(self.sched, z_ref, noise,
                                       strength=self.strength)
        z = ddim_sample(eps, self.sched, z_ref.shape, ctx,
                        jax.random.PRNGKey(0), steps=steps,
                        x_init=x_init, t_start=t_start)
        return vae_mod.decode(vae, self.vae_cfg, z / self.latent_scale)

    def _resume_core(self, net, vae, latent, ctx, steps_total: int, k: int):
        eps = dit_mod.make_eps_fn(net, self.net_cfg)
        z = resume_sample(eps, self.sched, latent, ctx, steps=steps_total,
                          k=k, strength=self.strength)
        return vae_mod.decode(vae, self.vae_cfg, z / self.latent_scale)

    def _step_slots_core(self, net, x, ctx, t, t_prev, active):
        # ONE ragged denoising step over the slot buffer: per-slot
        # timesteps, inactive slots pass through (see sampler.step_slots)
        eps = dit_mod.make_eps_fn(net, self.net_cfg)
        return step_slots(eps, self.sched, x, ctx, t, t_prev, active)

    def _slot_noise_core(self, seeds):
        # txt2img slot init: EXACTLY _txt2img_core's per-seed noise draw,
        # so a slot trajectory starts where the batched sampler would
        el_shape = (self.net_cfg.img_res, self.net_cfg.img_res,
                    self.net_cfg.in_ch)

        def _noise(seed):
            k_noise, _ = jax.random.split(jax.random.PRNGKey(seed))
            return jax.random.normal(k_noise, (1,) + el_shape)[0]

        return jax.vmap(_noise)(seeds)

    def _slot_img_init_core(self, vae, ref_img, seeds):
        # img2img slot init: _img2img_core's encode + per-seed noise +
        # SDEdit start, stopping BEFORE the chain (the chain runs in the
        # step-level engine, one step_slots launch per boundary)
        mean, _ = vae_mod.encode(vae, self.vae_cfg, ref_img)
        z_ref = mean * self.latent_scale

        def _noise(seed, z1):
            k1, _ = jax.random.split(jax.random.PRNGKey(seed))
            return jax.random.normal(k1, (1,) + z1.shape)[0]

        noise = jax.vmap(_noise)(seeds, z_ref)
        x_init, _ = sdedit_start(self.sched, z_ref, noise,
                                 strength=self.strength)
        return x_init

    def _slot_decode_core(self, vae, z):
        return vae_mod.decode(vae, self.vae_cfg, z / self.latent_scale)

    def _archive_latents_core(self, vae, images, seeds, depths, steps_total):
        # noised intermediates of the img2img chain each image WOULD run:
        # the same encode + per-seed noise draw as _img2img_core, pushed
        # to resume_noise_levels()[k] — depth 0 equals sdedit_start's
        # x_init exactly, so resume(k=0) replays full img2img
        mean, _ = vae_mod.encode(vae, self.vae_cfg, images)
        z0 = mean * self.latent_scale

        def _noise(seed, z1):
            k1, _ = jax.random.split(jax.random.PRNGKey(seed))
            return jax.random.normal(k1, (1,) + z1.shape)[0]

        noise = jax.vmap(_noise)(seeds, z0)
        levels = resume_noise_levels(self.sched, steps=steps_total,
                                     strength=self.strength)
        b = images.shape[0]
        return jnp.stack([
            self.sched.q_sample(z0, jnp.full((b,), levels[k], jnp.int32),
                                noise)
            for k in depths])

    # -- AOT bucket management -----------------------------------------------

    def _get(self, kind: str, steps: int, batch: int):
        """The compiled program of one (kind, steps, batch) bucket,
        compiled on first use inside a ``compile`` span.  Each program's
        function carries the kind's name, so its XLA module reads
        ``jit_<name>`` in a device trace (``jit_step_slots``,
        ``jit_slot_decode``, ``jit_resume``, ...)."""
        key = (kind, steps, batch)
        if key not in self._compiled:
            with span("compile", kind=kind, batch=batch):
                t0 = time.perf_counter()
                fn, args = self._program(kind, steps, batch)
                self._compiled[key] = jax.jit(fn).lower(
                    *jax.tree_util.tree_map(_to_sds, args)).compile()
                self.compile_seconds[key] = time.perf_counter() - t0
        return self._compiled[key]

    def _program(self, kind: str, steps: int, batch: int):
        """``(fn, example args)`` of one bucket: ``fn`` is a named
        function over the bucket's arguments, with ``steps``, ``batch``
        and the kind's parameters closed over."""
        res = self.image_res
        cfg = self.net_cfg
        lat_sds = jax.ShapeDtypeStruct(
            (batch, cfg.img_res, cfg.img_res, cfg.in_ch), jnp.float32)
        ctx_sds = jax.ShapeDtypeStruct((batch, cfg.ctx_dim), jnp.float32)
        img_sds = jax.ShapeDtypeStruct((batch, res, res, 3), jnp.float32)
        seeds_sds = jax.ShapeDtypeStruct((batch,), jnp.int32)
        if kind == "txt2img":
            def txt2img(n, v, c, s):
                return self._txt2img_core(n, v, c, s, steps, batch)
            return txt2img, (self.net_params, self.vae_params, ctx_sds,
                             seeds_sds)
        if kind.startswith("resume@"):
            k = int(kind.split("@", 1)[1])

            def resume(n, v, l, c):
                return self._resume_core(n, v, l, c, steps, k)
            return resume, (self.net_params, self.vae_params, lat_sds,
                            ctx_sds)
        if kind.startswith("latents@"):
            depths = tuple(int(d) for d in kind.split("@", 1)[1].split(","))

            def latents(v, i, s):
                return self._archive_latents_core(v, i, s, depths, steps)
            return latents, (self.vae_params, img_sds, seeds_sds)
        if kind == "step_slots":
            # steps is 0 for slot kinds: ONE compiled program per slot
            # capacity covers every mixture of per-slot step counts
            def step_slots(n, x, c, t, tp, a):
                return self._step_slots_core(n, x, c, t, tp, a)
            steps_sds = jax.ShapeDtypeStruct((batch,), jnp.int32)
            return step_slots, (self.net_params, lat_sds, ctx_sds,
                                steps_sds, steps_sds,
                                jax.ShapeDtypeStruct((batch,), jnp.bool_))
        if kind == "slot_noise":
            def slot_noise(s):
                return self._slot_noise_core(s)
            return slot_noise, (seeds_sds,)
        if kind == "slot_img_init":
            def slot_img_init(v, r, s):
                return self._slot_img_init_core(v, r, s)
            return slot_img_init, (self.vae_params, img_sds, seeds_sds)
        if kind == "slot_decode":
            def slot_decode(v, z):
                return self._slot_decode_core(v, z)
            return slot_decode, (self.vae_params, lat_sds)

        def img2img(n, v, r, c, s):
            return self._img2img_core(n, v, r, c, s, steps)
        return img2img, (self.net_params, self.vae_params, img_sds, ctx_sds,
                         seeds_sds)

    def precompile(self, *, step_buckets: Sequence[int] = (20, 30),
                   batch_buckets: Sequence[int] = (1,),
                   kinds: Sequence[str] = ("txt2img", "img2img")) -> float:
        """Compile every serving bucket up front; returns total seconds.
        This removes generation-path cold starts entirely.  ``kinds``
        restricts the workflow sweep when a policy pins each workflow to
        one step count (txt2img at steps_full, img2img at steps_ref)."""
        t0 = time.perf_counter()
        for b in batch_buckets:
            for s in step_buckets:
                for kind in kinds:
                    self._get(kind, s, b)
        return time.perf_counter() - t0

    def precompile_step_level(self, slot_capacity: int) -> float:
        """Compile the step-level serving buckets: ONE ``step_slots``
        program at the slot capacity (covering every ragged step mixture)
        plus the batch-of-one slot init/decode programs.  Returns total
        seconds."""
        t0 = time.perf_counter()
        self._get("step_slots", 0, slot_capacity)
        self._get("slot_noise", 0, 1)
        self._get("slot_img_init", 0, 1)
        self._get("slot_decode", 0, 1)
        return time.perf_counter() - t0

    def make_slot_engine(self, capacity: int) -> "DiffusionSlotEngine":
        return DiffusionSlotEngine(self, capacity)

    # -- GenerationBackend interface ------------------------------------------

    def txt2img(self, prompt: str, steps: int, seed: int) -> np.ndarray:
        ctx = jnp.asarray(self.embed_prompt(prompt), jnp.float32)[None]
        fn = self._get("txt2img", steps, 1)
        out = fn(self.net_params, self.vae_params, ctx,
                 jnp.asarray([seed], jnp.int32))
        return np.asarray(out[0])

    def img2img(self, prompt: str, reference: np.ndarray, steps: int,
                seed: int) -> np.ndarray:
        ctx = jnp.asarray(self.embed_prompt(prompt), jnp.float32)[None]
        fn = self._get("img2img", steps, 1)
        out = fn(self.net_params, self.vae_params,
                 jnp.asarray(reference, jnp.float32)[None], ctx,
                 jnp.asarray([seed], jnp.int32))
        return np.asarray(out[0])

    # -- batched entry points --------------------------------------------------

    @staticmethod
    def _bucket(n: int) -> int:
        """Pad a group to the next power-of-two AOT bucket so a handful of
        compiled programs covers every batch size."""
        return next_pow2(n)

    def _pad_ctx_seeds(self, prompts: Sequence[str], seeds: Sequence[int],
                       bucket: int):
        ctx = np.stack([np.asarray(self.embed_prompt(p), np.float32)
                        for p in prompts])
        pad = bucket - len(prompts)
        if pad:
            ctx = np.concatenate([ctx, np.repeat(ctx[-1:], pad, axis=0)])
        seeds_arr = np.asarray(list(seeds) + [0] * pad, np.int32)
        return jnp.asarray(ctx), jnp.asarray(seeds_arr)

    def txt2img_batch(self, prompts: Sequence[str], steps: int,
                      seeds: Sequence[int]) -> np.ndarray:
        """Batched text-to-image: one padded AOT call for the whole group.
        Element i equals ``txt2img(prompts[i], steps, seeds[i])`` up to XLA
        batching numerics (identical noise trajectories by construction)."""
        n = len(prompts)
        if n == 0:
            return np.zeros((0, self.image_res, self.image_res, 3),
                            np.float32)
        bucket = self._bucket(n)
        ctx, seeds_arr = self._pad_ctx_seeds(prompts, seeds, bucket)
        fn = self._get("txt2img", steps, bucket)
        out = fn(self.net_params, self.vae_params, ctx, seeds_arr)
        return np.asarray(out[:n])

    def img2img_batch(self, prompts: Sequence[str], references: np.ndarray,
                      steps: int, seeds: Sequence[int]) -> np.ndarray:
        """Batched SDEdit img2img over stacked references (B, H, W, 3)."""
        n = len(prompts)
        if n == 0:
            return np.zeros((0, self.image_res, self.image_res, 3),
                            np.float32)
        bucket = self._bucket(n)
        ctx, seeds_arr = self._pad_ctx_seeds(prompts, seeds, bucket)
        refs = np.asarray(references, np.float32)
        pad = bucket - n
        if pad:
            refs = np.concatenate([refs, np.repeat(refs[-1:], pad, axis=0)])
        fn = self._get("img2img", steps, bucket)
        out = fn(self.net_params, self.vae_params, jnp.asarray(refs), ctx,
                 seeds_arr)
        return np.asarray(out[:n])

    # -- latent-depth cache surface -------------------------------------------

    def resume_batch(self, prompts: Sequence[str], latents: np.ndarray,
                     steps_total: int, k: int,
                     seeds: Sequence[int]) -> np.ndarray:
        """Resume the ``steps_total``-step img2img chain from depth ``k``
        for a stacked batch of archived latents (no noise draw — the
        latents are pre-noised at archive time, so ``seeds`` only shapes
        the padding)."""
        n = len(prompts)
        if n == 0:
            return np.zeros((0, self.image_res, self.image_res, 3),
                            np.float32)
        bucket = self._bucket(n)
        ctx, _ = self._pad_ctx_seeds(prompts, seeds, bucket)
        lats = np.asarray(latents, np.float32)
        pad = bucket - n
        if pad:
            lats = np.concatenate([lats, np.repeat(lats[-1:], pad, axis=0)])
        fn = self._get(f"resume@{int(k)}", steps_total, bucket)
        out = fn(self.net_params, self.vae_params, jnp.asarray(lats), ctx)
        return np.asarray(out[:n])

    def archive_latents_batch(self, images: np.ndarray,
                              seeds: Sequence[int],
                              depths: Sequence[int],
                              steps_total: int) -> np.ndarray:
        """Noised img2img-chain intermediates of each image at every
        requested depth — ``(len(depths), B, img_res, img_res, in_ch)``.
        The per-image noise reuses the archive ``seed`` through the SAME
        draw as ``_img2img_core``, so depth 0 is bitwise the SDEdit
        initial state of ``img2img(image, seed)``."""
        imgs = np.asarray(images, np.float32)
        n = imgs.shape[0]
        if n == 0:
            return np.zeros((len(depths), 0, self.net_cfg.img_res,
                             self.net_cfg.img_res, self.net_cfg.in_ch),
                            np.float32)
        bucket = self._bucket(n)
        pad = bucket - n
        if pad:
            imgs = np.concatenate([imgs, np.repeat(imgs[-1:], pad, axis=0)])
        seeds_arr = jnp.asarray(np.asarray(list(seeds) + [0] * pad,
                                           np.int32))
        kind = "latents@" + ",".join(str(int(d)) for d in depths)
        fn = self._get(kind, steps_total, bucket)
        out = fn(self.vae_params, jnp.asarray(imgs), seeds_arr)
        return np.asarray(out)[:, :n]

    def as_generation_backend(self) -> GenerationBackend:
        """Compatibility shim: DiffusionBackend now IS a GenerationBackend
        (batch-first protocol), so this is the identity."""
        return self


def _to_sds(x):
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))


# ---------------------------------------------------------------------------
# step-level slot engines (ragged in-flight set, one denoising step / call)
# ---------------------------------------------------------------------------


class DiffusionSlotEngine:
    """Persistent step-wise sampler over a fixed-capacity slot buffer.

    Each occupied slot holds one in-flight generation request's latent,
    conditioning vector and DDIM timestep sub-sequence; every
    :meth:`step` call advances ALL active slots one denoising step through
    a single AOT-compiled ``("step_slots", 0, capacity)`` launch with
    per-slot timesteps, so requests with mixed step counts (K-step
    txt2img misses, truncated img2img band hits, ``resume@k`` latent-depth
    hits) enter and retire at ANY step boundary.

    Slot init reuses the batched cores' exact seed→noise draws
    (``slot_noise`` / ``slot_img_init``) and the per-kind timestep
    geometry of ``ddim_sample`` / ``resume_sample``, so a slot trajectory
    is the same chain the group sampler would run — only the launch
    granularity changes.  ``progress[handle]`` records the slot's step
    index after each advance (strictly monotone; pinned by the
    ragged-admission property suite) and ``step_calls`` counts compiled
    launches (exactly one executable per slot capacity)."""

    def __init__(self, backend: "DiffusionBackend", capacity: int):
        self.backend = backend
        self.capacity = int(capacity)
        cfg = backend.net_cfg
        self._lat = np.zeros((capacity, cfg.img_res, cfg.img_res,
                              cfg.in_ch), np.float32)
        self._ctx = np.zeros((capacity, cfg.ctx_dim), np.float32)
        self._active = np.zeros((capacity,), bool)
        self._ts: List[Optional[np.ndarray]] = [None] * capacity
        self._pos = [0] * capacity
        self._state: List[Optional[object]] = [None] * capacity
        self._handle = [-1] * capacity
        self.progress: Dict[int, List[int]] = {}
        self.step_calls = 0

    def free_count(self) -> int:
        return int(self.capacity - self._active.sum())

    def active_count(self) -> int:
        return int(self._active.sum())

    def admit(self, state, handle: int) -> None:
        """Seat one planned ``gen`` request in a free slot: compute its
        initial latent (per-request seed-noise semantics preserved) and
        its DDIM timestep sub-sequence.  One ``slot.seat`` span, ``kind``
        naming how the latent starts: ``noise`` (txt2img), ``img_init``
        (img2img) or ``resume`` (an archived latent)."""
        plan = state.plan
        slot = int(np.argmin(self._active))
        if self._active[slot]:
            raise RuntimeError("slot engine is full")
        kind = ("resume" if plan.latent is not None
                else "img_init" if plan.ref is not None else "noise")
        b = self.backend
        seeds = jnp.asarray([state.seed], jnp.int32)
        with span("slot.seat", req=int(handle), kind=kind):
            if plan.latent is not None:
                # resume@k: the last steps of the steps_total-step truncated
                # img2img chain (same geometry as resume_sample)
                steps_total = int(plan.steps) + int(plan.resume_k)
                ts = ddim_timesteps(b.sched.T, steps_total,
                                    t_start=int(b.strength * b.sched.T))
                ts = np.asarray(ts[int(plan.resume_k):])
                x0 = np.asarray(plan.latent, np.float32)
            elif plan.ref is not None:
                ts = np.asarray(ddim_timesteps(
                    b.sched.T, int(plan.steps),
                    t_start=int(b.strength * b.sched.T)))
                fn = b._get("slot_img_init", 0, 1)
                x0 = np.asarray(fn(b.vae_params,
                                   jnp.asarray(plan.ref, jnp.float32)[None],
                                   seeds)[0])
            else:
                ts = np.asarray(ddim_timesteps(b.sched.T, int(plan.steps)))
                fn = b._get("slot_noise", 0, 1)
                x0 = np.asarray(fn(seeds)[0])
            self._lat[slot] = x0
            self._ctx[slot] = np.asarray(b.embed_prompt(state.prompt),
                                         np.float32)
        self._ts[slot] = ts
        self._pos[slot] = 0
        self._state[slot] = state
        self._handle[slot] = int(handle)
        self._active[slot] = True
        self.progress[int(handle)] = [0]

    def step(self) -> List[Tuple[int, object]]:
        """Advance every active slot one DDIM step (one compiled launch);
        decode and free slots whose chain just finished.  Returns the
        retired ``(handle, state)`` pairs (``state.image`` set).

        One ``slot.step`` span (``active`` slots) whose children split
        the host round trip: ``slot.upload`` (the slot buffer and
        timesteps to the device), ``slot.launch`` (the program, to its
        end), ``slot.download`` (the buffer back) and one ``slot.decode``
        per retiring slot (``req``)."""
        b = self.backend
        with span("slot.step", active=self.active_count()):
            t = np.zeros((self.capacity,), np.int32)
            tp = np.full((self.capacity,), -1, np.int32)
            for i in range(self.capacity):
                if not self._active[i]:
                    continue
                ts, p = self._ts[i], self._pos[i]
                t[i] = ts[p]
                tp[i] = ts[p + 1] if p + 1 < len(ts) else -1
            fn = b._get("step_slots", 0, self.capacity)
            with span("slot.upload"):
                args = (jnp.asarray(self._lat), jnp.asarray(self._ctx),
                        jnp.asarray(t), jnp.asarray(tp),
                        jnp.asarray(self._active))
            with span("slot.launch"):
                out = jax.block_until_ready(fn(b.net_params, *args))
            with span("slot.download"):
                self._lat = np.array(out)   # copy: the buffer stays writable
            self.step_calls += 1
            retired: List[Tuple[int, object]] = []
            dec = b._get("slot_decode", 0, 1)
            for i in range(self.capacity):
                if not self._active[i]:
                    continue
                self._pos[i] += 1
                self.progress[self._handle[i]].append(self._pos[i])
                if self._pos[i] >= len(self._ts[i]):
                    with span("slot.decode", req=self._handle[i]):
                        z = jnp.asarray(self._lat[i])[None]
                        img = np.asarray(dec(b.vae_params, z)[0])
                    st = self._state[i]
                    st.image = img
                    retired.append((self._handle[i], st))
                    self._active[i] = False
                    self._ts[i] = None
                    self._state[i] = None
                    self._handle[i] = -1
        return retired


class EmulatedSlotEngine:
    """Slot-engine surface for generic :class:`GenerationBackend`\\ s (no
    resident latent state).  Each admitted request's image is computed at
    admission as a batch of ONE — element-for-element the call sequential
    ``serve`` makes, so step-level serving stays bitwise-identical on any
    deterministic backend — and the slot then counts down its plan's step
    budget so admission/retirement interleaving (and therefore clock,
    archive and maintenance order) matches the real slot engine's ragged
    schedule."""

    def __init__(self, system: CacheGenius, capacity: int):
        self.system = system
        self.capacity = int(capacity)
        self._remaining: List[int] = [0] * capacity
        self._state: List[Optional[object]] = [None] * capacity
        self._handle = [-1] * capacity
        self._active = np.zeros((capacity,), bool)
        self.progress: Dict[int, List[int]] = {}
        self.step_calls = 0

    def free_count(self) -> int:
        return int(self.capacity - self._active.sum())

    def active_count(self) -> int:
        return int(self._active.sum())

    def admit(self, state, handle: int) -> None:
        backend = self.system.backend
        plan = state.plan
        slot = int(np.argmin(self._active))
        if self._active[slot]:
            raise RuntimeError("slot engine is full")
        if plan.latent is not None:
            img = backend.resume_batch(
                [state.prompt], np.asarray(plan.latent)[None],
                int(plan.steps) + int(plan.resume_k), int(plan.resume_k),
                [state.seed])[0]
        elif plan.ref is not None:
            img = backend.img2img_batch(
                [state.prompt], np.asarray(plan.ref)[None],
                int(plan.steps), [state.seed])[0]
        else:
            img = backend.txt2img_batch(
                [state.prompt], int(plan.steps), [state.seed])[0]
        state.image = np.asarray(img)
        self._remaining[slot] = max(int(plan.steps), 1)
        self._state[slot] = state
        self._handle[slot] = int(handle)
        self._active[slot] = True
        self.progress[int(handle)] = [0]

    def step(self) -> List[Tuple[int, object]]:
        self.step_calls += 1
        retired: List[Tuple[int, object]] = []
        for i in range(self.capacity):
            if not self._active[i]:
                continue
            self._remaining[i] -= 1
            h = self._handle[i]
            self.progress[h].append(self.progress[h][-1] + 1)
            if self._remaining[i] <= 0:
                retired.append((h, self._state[i]))
                self._active[i] = False
                self._state[i] = None
                self._handle[i] = -1
        return retired


# ---------------------------------------------------------------------------
# batched request engine
# ---------------------------------------------------------------------------


@dataclass
class Request:
    prompt: str
    seed: int = 0
    quality_tier: bool = False
    submitted_at: float = 0.0   # perf_counter (drain) / virtual clock (run)
    # multi-tenant tags (None = untagged single-tenant traffic): set by
    # the front-door gateway and by tagged arrival processes; surfaced in
    # the per-(tenant, tier) latency percentiles (tenant_tier_stats)
    tenant: Optional[str] = None
    tier: Optional[str] = None


@dataclass
class Completed:
    request: Request
    result: ServeResult
    queue_delay: float          # seconds actually waited before admission
    finished_at: float = 0.0    # engine-clock instant the result came back
    # step-level only: engine-clock seconds from the instant the result
    # was ready to the start of its finalize, i.e. the time it was held
    # by submission-order release alone (0 in group mode, which has no
    # such gate)
    release_wait: float = 0.0


class ServingEngine:
    """Asynchronous-queue semantics (paper §V "asynchronous task queue")
    over ``CacheGenius.serve_batch``.

    ``run`` is the continuous-batching event loop over a timestamped
    arrival process; ``submit`` + ``drain`` is the legacy closed-loop
    surface (everything queued up front, FIFO micro-batches of
    ``max_batch``).  See the module docstring for the two draining
    disciplines and the timing/parity invariants.
    """

    def __init__(self, system: CacheGenius, *, max_batch: int = 8):
        self.system = system
        self.max_batch = max_batch
        self.queue: List[Request] = []
        self.completed: List[Completed] = []
        # step-level telemetry: active-slot count sampled before every
        # step launch of the most recent step_level=True run, plus the
        # engine itself (step_calls / progress / capacity introspection)
        self.slot_occupancy: List[int] = []
        self.last_slot_engine: Optional[object] = None
        # Maintenance intervals smaller than max_batch are honoured: the
        # Finish stage sweeps at exact request-count crossings (archives
        # past a crossing are deferred to the per-request result loop),
        # so the sweep cadence no longer depends on batch partitioning
        # and the old clamp-to-max_batch is gone.

    # -- legacy closed-loop surface -------------------------------------------

    def submit(self, prompt: str, *, seed: int = 0,
               quality_tier: bool = False) -> None:
        self.queue.append(Request(prompt, seed, quality_tier,
                                  submitted_at=time.perf_counter()))

    def serve_group(self, batch: Sequence[Request]) -> List[Completed]:
        """Serve ONE micro-batch (one step group) right now, wall-clock.

        This is the group-boundary primitive the front-door dispatcher
        pumps (``repro.frontdoor.dispatcher``): requests go through one
        staged-pipeline pass, ``queue_delay`` reports submission →
        pipeline admission on ``time.perf_counter`` (the clock
        ``submitted_at`` must be on), and completions are appended to
        ``self.completed`` in submission order.
        """
        if not batch:
            return []
        results = self.system.serve_batch(
            [r.prompt for r in batch],
            seeds=[r.seed for r in batch],
            quality_tiers=[r.quality_tier for r in batch],
            submitted_ats=[r.submitted_at for r in batch])
        done_at = time.perf_counter()
        out = [Completed(req, res, queue_delay=res.queue_delay,
                         finished_at=done_at)
               for req, res in zip(batch, results)]
        self.completed.extend(out)
        return out

    def drain(self) -> List[Completed]:
        """Serve the whole queue in FIFO micro-batches of ``max_batch``.

        ``queue_delay`` is the time each request ACTUALLY waited: from its
        ``submit`` instant to its micro-batch's pipeline admission, both on
        ``time.perf_counter`` (earlier revisions reported submission-clock
        ticks).  Within a micro-batch later submissions waited less; across
        micro-batches delays grow by the service time of the batches ahead.
        """
        out = []
        while self.queue:
            batch, self.queue = (self.queue[: self.max_batch],
                                 self.queue[self.max_batch:])
            out.extend(self.serve_group(batch))
        return out

    # -- continuous batching ----------------------------------------------------

    def run(self, arrivals: Iterable[TimedRequest], *,
            mode: str = "continuous", start: float = 0.0,
            step_level: bool = False, slot_capacity: Optional[int] = None,
            on_step: Optional[Callable[[int], None]] = None,
            ) -> List[Completed]:
        """Serve a timestamped arrival process; returns arrival order.

        The virtual clock starts at ``start`` and advances two ways: idling
        to the next arrival when nothing is queued, and by the MEASURED wall
        time of each staged-pipeline pass while serving — so simulated
        arrival gaps and real compute compose on one timeline.  When
        splitting one trace across several ``run`` calls (e.g. to fail a
        node between halves), pass the previous call's final
        ``finished_at`` as ``start`` so backlog carries over instead of the
        clock rewinding to the next arrival.

        ``mode="continuous"`` admits everything that has arrived (up to
        ``max_batch``) into the next generation bucket the moment the
        in-flight group completes.  ``mode="drain"`` is the fixed-drain
        baseline: a bucket closes only once ``max_batch`` requests have
        arrived (or the trace is exhausted), so a request that just misses
        a closure waits for the bucket to fill — a full burst period under
        bursty traffic.

        Each ``Completed`` carries ``queue_delay`` = admission instant −
        arrival instant on the virtual clock (also stamped onto
        ``result.queue_delay``, overriding the pipeline's perf-counter
        figure, which has no meaning on a virtual timeline) and
        ``finished_at`` = the group's completion instant.

        ``step_level=True`` (continuous mode only) switches admission from
        step-GROUP to step granularity: a persistent slot engine of
        ``slot_capacity`` slots (default ``max_batch``) advances every
        in-flight generation one denoising step per launch, admitting
        arrivals into free slots at ANY step boundary and retiring
        finished slots through per-request Archive/Finish passes in
        submission order (exact maintenance crossings preserved).
        ``on_step(step_no)`` is the fault-injection hook (e.g.
        ``fail_node`` / chaos injection while work is in flight): with
        ``step_level=True`` it is called before each step launch; in
        group mode it is called before each GROUP is served (the group
        counter stands in for the step number — group granularity is the
        finest boundary that mode has).  See :class:`DiffusionSlotEngine`
        / :class:`EmulatedSlotEngine` and ``docs/ARCHITECTURE.md``.
        """
        if mode not in ("continuous", "drain"):
            raise ValueError(f"unknown mode {mode!r}")
        if step_level and mode != "continuous":
            raise ValueError("step_level=True requires mode='continuous'")
        if not step_level and slot_capacity is not None:
            raise ValueError(
                "slot_capacity only applies with step_level=True")
        if self.queue:
            raise RuntimeError(
                "ServingEngine.run would strand the submit() queue "
                f"({len(self.queue)} pending requests) — drain() it first")
        if step_level:
            return self._run_step_level(
                arrivals, start=start,
                slot_capacity=slot_capacity or self.max_batch,
                on_step=on_step)
        pending = deque(sorted(arrivals, key=lambda a: a.arrival_time))
        ready: List[TimedRequest] = []
        out: List[Completed] = []
        now = float(start)
        group_no = 0

        def admit_arrived() -> None:
            while pending and pending[0].arrival_time <= now + 1e-12:
                ready.append(pending.popleft())

        with span("serve.run", requests=len(pending)):
            while pending or ready:
                admit_arrived()
                if mode == "drain":
                    while len(ready) < self.max_batch and pending:
                        now = max(now, pending[0].arrival_time)
                        admit_arrived()
                if not ready:
                    now = max(now, pending[0].arrival_time)
                    continue
                batch, ready = ready[: self.max_batch], ready[self.max_batch:]
                if on_step is not None:
                    on_step(group_no)
                group_no += 1
                admitted = now
                t0 = time.perf_counter()
                results = self.system.serve_batch(
                    [r.prompt for r in batch],
                    seeds=[r.seed for r in batch],
                    quality_tiers=[r.quality_tier for r in batch])
                now = admitted + (time.perf_counter() - t0)
                for r, res in zip(batch, results):
                    res.queue_delay = admitted - r.arrival_time
                    req = Request(r.prompt, r.seed, r.quality_tier,
                                  submitted_at=r.arrival_time,
                                  tenant=r.tenant, tier=r.tier)
                    out.append(Completed(req, res, queue_delay=res.queue_delay,
                                         finished_at=now))
        self.completed.extend(out)
        return out

    def _run_step_level(self, arrivals: Iterable[TimedRequest], *,
                        start: float, slot_capacity: int,
                        on_step: Optional[Callable[[int], None]],
                        ) -> List[Completed]:
        """Step-level continuous batching over a persistent slot engine.

        Event loop invariants (the ragged-admission property suite pins
        each of these against group-continuous and sequential ``serve``):

        * ADMISSION — whenever slots are free and requests have arrived,
          one Embed..Plan pass (``ServePipeline.run_admission``) plans the
          admission group against the current cache snapshot; ``gen``
          plans are seated in slots, everything else completes
          immediately.  Earlier unfinalized gen requests seed the Plan
          stage's coalescing set, so a near-duplicate arriving mid-flight
          aliases onto the in-flight slot exactly as it would alias
          inside one group.
        * RETIREMENT — a slot retires the step its chain ends; the image
          is decoded per slot, but Archive/Finish run in SUBMISSION order
          (``ServePipeline.finalize`` per request), so blob ids, history
          records, eviction sweeps at exact maintenance crossings, and
          per-request stats all match the sequential loop regardless of
          retirement interleaving.
        * TIMING — the virtual clock advances by the measured wall time
          of every admission pass, step launch, and finalize pass;
          ``queue_delay`` is admission instant − arrival instant, and
          per-request ``wall_total`` / ``stage_walls`` are stamped from
          the slot's OWN timestamp trail (never group-smeared).
        * FAULTS — a node death mid-flight (``on_step`` → ``fail_node``)
          never loses an accepted job: occupied slots finish their chain
          and their archive/accounting reroute to a surviving node at
          finalize, leaving the dead node's VectorDB untouched.
        """
        system = self.system
        make = getattr(system.backend, "make_slot_engine", None)
        engine = (make(slot_capacity) if make is not None
                  else EmulatedSlotEngine(system, slot_capacity))
        self.last_slot_engine = engine
        self.slot_occupancy = []
        pending = deque(sorted(arrivals, key=lambda a: a.arrival_time))
        ready: List[TimedRequest] = []
        out: List[Completed] = []
        now = float(start)
        states: Dict[int, object] = {}
        arr_of: Dict[int, TimedRequest] = {}
        admit_t: Dict[int, float] = {}
        img_ready: Dict[int, bool] = {}
        ready_at: Dict[int, float] = {}   # engine clock: result ready
        alias_target: Dict[int, int] = {}
        inflight_gen: List[int] = []   # unfinalized gen handles, ascending
        next_handle = 0
        next_fin = 0
        step_no = 0

        def admit_arrived() -> None:
            while pending and pending[0].arrival_time <= now + 1e-12:
                ready.append(pending.popleft())

        def do_admission() -> None:
            nonlocal now, next_handle
            free = engine.free_count()
            batch, rest = ready[:free], ready[free:]
            ready[:] = rest
            base = next_handle
            admitted = now
            inflight = [(states[h].qvec, h) for h in inflight_gen]
            t0 = time.perf_counter()
            with span("serve.admit", first_req=base, n=len(batch),
                      free=free):
                planned = system.pipeline.run_admission(
                    system, [r.prompt for r in batch],
                    seeds=[r.seed for r in batch],
                    quality_tiers=[r.quality_tier for r in batch],
                    inflight=inflight or None)
                for s, r in zip(planned, batch):
                    h = base + s.index
                    states[h], arr_of[h], admit_t[h] = s, r, admitted
                    if s.plan.kind == "gen":
                        self._admit_with_retry(engine, s, h)
                        inflight_gen.append(h)
                        img_ready[h] = False
                    elif s.plan.kind == "alias":
                        t = s.plan.target
                        alias_target[h] = base + t if t >= 0 else -(t + 1)
            next_handle += len(batch)
            now = admitted + (time.perf_counter() - t0)
            for h in range(base, next_handle):
                if states[h].plan.kind != "gen":
                    ready_at[h] = now

        def finalize_due() -> None:
            nonlocal now, next_fin
            while next_fin < next_handle:
                h = next_fin
                st = states[h]
                if st.plan.kind == "gen" and not img_ready[h]:
                    break      # submission-order gate: wait for the slot
                if st.plan.kind == "alias":
                    # ready once its target's image is
                    ready_at[h] = max(ready_at[h],
                                      ready_at[alias_target[h]])
                    # target is an earlier gen request — already retired
                    # (and finalized) by the submission-order gate, so its
                    # image is available; this is the history fast path
                    # sequential serve takes once the target is recorded
                    st.plan = Plan(kind="history",
                                   image=states[alias_target[h]].image)
                elif st.plan.kind == "gen":
                    node = st.plan.node
                    if (0 <= node < len(system.dbs)
                            and not system.scheduler.nodes[node].alive):
                        alive = [i for i in range(len(system.dbs))
                                 if system.scheduler.nodes[i].alive]
                        if alive:   # reroute archive + accounting off the
                            st.plan.node = alive[0]   # dead node's VDB
                # held by submission order alone since it was ready
                wait = now - ready_at[h]
                t0 = time.perf_counter()
                with span("serve.finalize", req=h, release_wait=wait):
                    system.pipeline.finalize(system, st)
                now += time.perf_counter() - t0
                r = arr_of[h]
                res = st.result
                res.queue_delay = admit_t[h] - r.arrival_time
                req = Request(r.prompt, r.seed, r.quality_tier,
                              submitted_at=r.arrival_time,
                              tenant=r.tenant, tier=r.tier)
                out.append(Completed(req, res, queue_delay=res.queue_delay,
                                     finished_at=now, release_wait=wait))
                if inflight_gen and inflight_gen[0] == h:
                    inflight_gen.pop(0)
                next_fin += 1

        with span("serve.run", requests=len(pending)):
            while pending or ready or next_fin < next_handle:
                admit_arrived()
                if ready and engine.free_count() > 0:
                    do_admission()
                    finalize_due()   # cached/history/alias complete now
                if engine.active_count() > 0:
                    if on_step is not None:
                        on_step(step_no)
                    self.slot_occupancy.append(engine.active_count())
                    t0 = time.perf_counter()
                    retired = engine.step()
                    now += time.perf_counter() - t0
                    step_no += 1
                    for h, st in retired:
                        st.stage_ts["Generate"] = time.perf_counter()
                        img_ready[h] = True
                        ready_at[h] = now
                    finalize_due()
                elif not ready:
                    finalize_due()
                    if pending:
                        now = max(now, pending[0].arrival_time)
                    elif next_fin >= next_handle:
                        break
        self.completed.extend(out)
        return out

    def _admit_with_retry(self, engine, state, handle: int) -> None:
        """Seat one gen plan in a slot, retrying transient backend faults
        (the emulated engine generates AT admit time — a batch-of-one
        backend call — so this is the step-level analogue of the Generate
        stage's retry loop).  Health bookkeeping mirrors
        ``GenerateStage._call``; the final failed attempt re-raises so no
        accepted job is silently dropped."""
        system = self.system
        retries = getattr(system, "transient_retries", 0)
        sched = (system.scheduler
                 if getattr(system, "use_scheduler", False) else None)
        node = state.plan.node
        attempt = 0
        while True:
            try:
                engine.admit(state, handle)
            except TransientBackendError:
                if sched is not None and 0 <= node < len(sched.nodes):
                    sched.observe_fault(node, kind="transient")
                stats = getattr(system, "stats", None)
                if stats is not None:
                    stats.transient_retries += 1
                attempt += 1
                if attempt > retries:
                    raise
                continue
            if sched is not None and 0 <= node < len(sched.nodes):
                sched.observe_ok(node)
            return

    def fail_node(self, node: int) -> None:
        self.system.fail_node(node)

    def join_node(self, *, speed: float = 1.0,
                  capacity: Optional[int] = None) -> int:
        """Grow the fleet by one fresh node (see ``CacheGenius
        .join_node``); returns the new node index.  Safe between groups —
        routing only consults the fleet at batch admission."""
        return self.system.join_node(speed=speed, capacity=capacity)

    def tagged_stats(self) -> Dict[Tuple[Optional[str], Optional[str]],
                                   Dict[str, float]]:
        """Per-(tenant, tier) latency percentiles over everything this
        engine has completed (empty when traffic is untagged) — see
        :func:`tenant_tier_stats`."""
        return tenant_tier_stats(self.completed)


def tenant_tier_stats(completed: Sequence[Completed],
                      ) -> Dict[Tuple[Optional[str], Optional[str]],
                                Dict[str, float]]:
    """Queue-delay and wall-latency percentiles per (tenant, tier).

    Groups tagged completions (requests whose ``tenant`` or ``tier`` is
    set) and reports, per group: ``n``, ``queue_delay_p50/p95``,
    ``wall_p50/p95`` (per-request measured pipeline wall ``wall_total``)
    and ``e2e_p50/p95``
    (queue delay + wall).  Untagged completions are skipped; fully
    untagged traffic returns ``{}``, which is the "don't print the
    table" signal the serve CLI keys on.
    """
    groups: Dict[Tuple[Optional[str], Optional[str]], List[Completed]] = {}
    for c in completed:
        if c.request.tenant is None and c.request.tier is None:
            continue
        groups.setdefault((c.request.tenant, c.request.tier), []).append(c)
    out: Dict[Tuple[Optional[str], Optional[str]], Dict[str, float]] = {}
    for key in sorted(groups, key=lambda k: (str(k[0]), str(k[1]))):
        cs = groups[key]
        qd = np.array([c.queue_delay for c in cs])
        wall = np.array([c.result.wall_total for c in cs])
        e2e = qd + wall
        out[key] = {
            "n": len(cs),
            "queue_delay_p50": float(np.percentile(qd, 50)),
            "queue_delay_p95": float(np.percentile(qd, 95)),
            "wall_p50": float(np.percentile(wall, 50)),
            "wall_p95": float(np.percentile(wall, 95)),
            "e2e_p50": float(np.percentile(e2e, 50)),
            "e2e_p95": float(np.percentile(e2e, 95)),
        }
    return out


# ---------------------------------------------------------------------------
# LM response cache (beyond-paper arch adaptation)
# ---------------------------------------------------------------------------


@dataclass
class LMResponseCache:
    """Semantic response cache for LM serving — the paper's HIT_RETURN
    branch ported to discrete tokens.  There is no img2img middle band:
    a near-miss cannot be 'partially denoised', so scores below the hit
    threshold always decode from scratch (and archive the result)."""

    embed: Callable[[str], np.ndarray]
    hit_threshold: float = 0.95
    capacity: int = 4096
    _vecs: np.ndarray = field(default=None, repr=False)  # type: ignore
    _responses: List[str] = field(default_factory=list, repr=False)
    hits: int = 0
    misses: int = 0

    def __post_init__(self):
        dim = len(np.asarray(self.embed("probe")).reshape(-1))
        self._vecs = np.zeros((0, dim), np.float32)

    def lookup(self, prompt: str) -> Optional[str]:
        if self._vecs.shape[0] == 0:
            self.misses += 1
            return None
        q = _l2n(np.asarray(self.embed(prompt), np.float32).reshape(-1))
        sims = self._vecs @ q
        i = int(np.argmax(sims))
        if sims[i] >= self.hit_threshold:
            self.hits += 1
            return self._responses[i]
        self.misses += 1
        return None

    def insert(self, prompt: str, response: str) -> None:
        q = _l2n(np.asarray(self.embed(prompt), np.float32).reshape(-1))
        self._vecs = np.concatenate([self._vecs, q[None]])[-self.capacity:]
        self._responses = (self._responses + [response])[-self.capacity:]

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / max(total, 1)


def _l2n(x: np.ndarray) -> np.ndarray:
    return x / max(float(np.linalg.norm(x)), 1e-12)
