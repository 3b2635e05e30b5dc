"""Program spans on the profiler's clock (``repro.runtime.tracing``).

A tiny step-level run through the real slot engine, recorded by
``jax.profiler`` on the CPU, must hold the ``cg:`` spans with the nesting
the trace reduction relies on: pipeline stages inside an admission pass
or a finalize, the eviction sweep inside ``stage.Finish``, and the slot
buffer's round trip inside ``slot.step``.  One request's spans carry one
``req``; ``stage_ts`` are the stage spans' ends; the served programs have
stable module names; ``Completed.release_wait`` measures the
submission-order gate.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.core.policy import GenerationPolicy
from repro.core.trace import RequestTrace, TimedRequest, bursty_arrivals
from repro.launch.serve import build_system
from repro.runtime.serving import ServingEngine
from repro.runtime.tracing import PREFIX, span

from test_step_level import tiny_diffusion_backend  # noqa: F401

SLOTS = 4


def _system(backend, *, interval=None):
    policy = GenerationPolicy(steps_full=2, steps_ref=2)
    system, _, _, _ = build_system(n_nodes=2, corpus_n=60,
                                   capacity_per_node=60, seed=0,
                                   policy=policy, backend=backend)
    if interval is not None:
        system.maintenance_interval = interval
    backend.precompile_step_level(SLOTS)
    return system


def _load_spans(log_dir):
    """``[(name, start_ns, end_ns, stats)]`` of the ``cg:`` host events."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = float(e.start_ns)
                    out.append((e.name[len(PREFIX):], s,
                                s + float(e.duration_ns), dict(e.stats)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _parent(sp, spans, names):
    """The innermost span named one of ``names`` that encloses ``sp``."""
    around = [p for p in spans if p[0] in names and p is not sp
              and _inside(sp, p)]
    return min(around, key=lambda p: p[2] - p[1]) if around else None


@pytest.fixture(scope="module")
def traced_run(tiny_diffusion_backend, tmp_path_factory):  # noqa: F811
    """One step-level run of 16 requests, traced: bursts behind in-flight
    chains, and a maintenance crossing every 5 requests."""
    system = _system(tiny_diffusion_backend, interval=5)
    finalized = []
    fin = system.pipeline.finalize

    def finalize(system_, state):
        finalized.append(state)
        return fin(system_, state)
    system.pipeline.finalize = finalize
    admitted = []
    admit = system.pipeline.run_admission

    def run_admission(*a, **k):
        states = admit(*a, **k)
        admitted.append(states)
        return states
    system.pipeline.run_admission = run_admission

    reqs = list(RequestTrace(seed=11).generate(16))
    log_dir = str(tmp_path_factory.mktemp("trace"))
    engine = ServingEngine(system, max_batch=SLOTS)
    jax.profiler.start_trace(log_dir)
    try:
        done = engine.run(bursty_arrivals(reqs, burst_size=4, burst_gap=0.5),
                          step_level=True, slot_capacity=SLOTS)
    finally:
        jax.profiler.stop_trace()
    return dict(done=done, spans=_load_spans(log_dir), finalized=finalized,
                admitted=admitted, system=system)


def test_run_holds_every_serving_span(traced_run):
    names = {sp[0] for sp in traced_run["spans"]}
    assert {"serve.run", "serve.admit", "serve.finalize", "scan",
            "maintain", "slot.seat", "slot.step", "slot.upload",
            "slot.launch", "slot.download", "slot.decode"} <= names
    assert {f"stage.{n}" for n in ("Embed", "Schedule", "Retrieve",
                                   "Score", "Plan", "Archive",
                                   "Finish")} <= names
    runs = [sp for sp in traced_run["spans"] if sp[0] == "serve.run"]
    assert len(runs) == 1 and runs[0][3] == {"requests": 16}
    done = traced_run["done"]
    assert len(done) == 16
    # the run exercised chains, not only cache fast paths
    assert any(c.result.steps > 0 for c in done)


@pytest.mark.parametrize("child,parents", [
    ("stage.Embed", ("serve.admit",)),
    ("stage.Schedule", ("serve.admit",)),
    ("stage.Retrieve", ("serve.admit",)),
    ("stage.Score", ("serve.admit",)),
    ("stage.Plan", ("serve.admit",)),
    ("scan", ("stage.Schedule",)),
    ("slot.seat", ("serve.admit",)),
    ("stage.Archive", ("serve.finalize",)),
    ("stage.Finish", ("serve.finalize",)),
    ("maintain", ("stage.Finish",)),
    ("slot.upload", ("slot.step",)),
    ("slot.launch", ("slot.step",)),
    ("slot.download", ("slot.step",)),
    ("slot.decode", ("slot.step",)),
    ("serve.admit", ("serve.run",)),
    ("serve.finalize", ("serve.run",)),
    ("slot.step", ("serve.run",)),
])
def test_span_nests_inside_its_parent(traced_run, child, parents):
    spans = traced_run["spans"]
    kids = [sp for sp in spans if sp[0] == child]
    assert kids, child
    for sp in kids:
        assert _parent(sp, spans, parents) is not None, (child, sp)


def test_stage_spans_carry_the_batch_size(traced_run):
    spans = traced_run["spans"]
    for sp in spans:
        if sp[0].startswith("stage."):
            parent = _parent(sp, spans, ("serve.admit", "serve.finalize"))
            want = parent[3]["n"] if parent[0] == "serve.admit" else 1
            assert sp[3] == {"n": want}


def test_maintain_counts_rows_and_evictions(traced_run):
    sweeps = [sp for sp in traced_run["spans"] if sp[0] == "maintain"]
    assert len(sweeps) == 3                       # crossings 5, 10, 15
    for sp in sweeps:
        assert sp[3]["rows"] > 0 and sp[3]["evicted"] >= 0


def test_maintain_reports_rows_scored(traced_run):
    """Every sweep carries ``scored``; the run's fleet stays within its
    budget, so no sweep scores a row or evicts one."""
    capacity = traced_run["system"].cache_capacity
    sweeps = [sp for sp in traced_run["spans"] if sp[0] == "maintain"]
    assert sweeps
    for sp in sweeps:
        assert sp[3]["rows"] <= capacity
        assert sp[3]["scored"] == 0 and sp[3]["evicted"] == 0


def test_over_budget_sweep_reports_every_row_scored(tmp_path):
    system, _, _, _ = build_system(n_nodes=2, corpus_n=40,
                                   capacity_per_node=40, seed=0)
    rows = system.total_size
    system.cache_capacity = rows - 3
    jax.profiler.start_trace(str(tmp_path))
    try:
        system.maintain()
    finally:
        jax.profiler.stop_trace()
    (_, _, _, stats), = [sp for sp in _load_spans(str(tmp_path))
                         if sp[0] == "maintain"]
    assert stats == {"rows": rows, "scored": rows, "evicted": 3}


def test_one_request_carries_one_req(traced_run):
    spans = traced_run["spans"]
    by_name = defaultdict(list)
    for sp in spans:
        if "req" in sp[3]:
            by_name[sp[0]].append(sp[3]["req"])
    finals = by_name["serve.finalize"]
    assert finals == list(range(16))              # submission order
    seats, decodes = by_name["slot.seat"], by_name["slot.decode"]
    assert seats and sorted(seats) == sorted(decodes)
    assert set(seats) <= set(finals)
    for sp in spans:
        if sp[0] == "slot.seat":
            assert sp[3]["kind"] in ("noise", "img_init", "resume")
    # an admission pass names the requests it admitted
    admits = [sp[3] for sp in spans if sp[0] == "serve.admit"]
    assert sum(a["n"] for a in admits) == 16
    assert [a["first_req"] for a in admits] == list(
        np.cumsum([0] + [a["n"] for a in admits[:-1]]))
    # a request seated in a slot was seated by the pass that admitted it
    for sp in spans:
        if sp[0] == "slot.seat":
            a = _parent(sp, spans, ("serve.admit",))[3]
            assert a["first_req"] <= sp[3]["req"] < a["first_req"] + a["n"]


def test_stage_ts_are_the_stage_span_ends(traced_run):
    """Every stamp of a request's trail is the end of the stage span that
    produced it, on one clock up to a fixed offset."""
    spans = traced_run["spans"]
    finals = [sp for sp in spans if sp[0] == "serve.finalize"]
    admits = [sp for sp in spans if sp[0] == "serve.admit"]
    pairs = []          # (stage_ts seconds, span end seconds)
    for state, fsp in zip(traced_run["finalized"], finals):
        for name in ("Archive", "Finish"):
            sp, = [s for s in spans if s[0] == "stage." + name
                   and _inside(s, fsp)]
            pairs.append((state.stage_ts[name], sp[2] * 1e-9))
    for states, asp in zip(traced_run["admitted"], admits):
        for name in ("Embed", "Schedule", "Retrieve", "Score", "Plan"):
            sp, = [s for s in spans if s[0] == "stage." + name
                   and _inside(s, asp)]
            for state in states:
                pairs.append((state.stage_ts[name], sp[2] * 1e-9))
    diff = np.array([end - ts for ts, end in pairs])
    assert len(diff) == 2 * 16 + 5 * 16
    assert np.abs(diff - np.median(diff)).max() < 1e-3


@pytest.mark.parametrize("key,module", [
    (("step_slots", 0, SLOTS), "jit_step_slots"),
    (("slot_decode", 0, 1), "jit_slot_decode"),
    (("slot_img_init", 0, 1), "jit_slot_img_init"),
    (("slot_noise", 0, 1), "jit_slot_noise"),
    (("txt2img", 2, 1), "jit_txt2img"),
    (("img2img", 2, 1), "jit_img2img"),
    (("resume@1", 2, 1), "jit_resume"),
    (("latents@0,1", 2, 1), "jit_latents"),
])
def test_served_programs_have_stable_module_names(tiny_diffusion_backend,
                                                  key, module):  # noqa: F811
    text = tiny_diffusion_backend._get(*key).as_text()
    assert text.split(",", 1)[0] == f"HloModule {module}"
    assert "lambda" not in text.split("\n", 1)[0]


def test_first_use_compiles_inside_a_compile_span(tmp_path,
                                                  tiny_diffusion_backend,
                                                  ):  # noqa: F811
    tiny_diffusion_backend._compiled.pop(("slot_noise", 0, 2), None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        tiny_diffusion_backend._get("slot_noise", 0, 2)
        tiny_diffusion_backend._get("slot_noise", 0, 2)     # cached
    finally:
        jax.profiler.stop_trace()
    compiles = [sp for sp in _load_spans(str(tmp_path))
                if sp[0] == "compile"]
    assert [sp[3] for sp in compiles] == [{"kind": "slot_noise",
                                           "batch": 2}]


def _timed(prompts_at):
    return [TimedRequest(t, p, seed=i) for i, (t, p) in enumerate(prompts_at)]


# on a fresh tiny system the first is a corpus hit, the second a chain
HIT = "a small cyan ring at the right on a gray background"
MISS = "a large white circle at the right on a olive background"


def test_release_wait_is_zero_for_a_hit_with_nothing_in_flight(
        tiny_diffusion_backend):  # noqa: F811
    system = _system(tiny_diffusion_backend)
    hit, = ServingEngine(system, max_batch=SLOTS).run(
        _timed([(0.0, HIT)]), step_level=True, slot_capacity=SLOTS)
    assert hit.result.steps == 0
    assert hit.release_wait == 0.0


def test_release_wait_counts_the_chain_a_hit_waits_behind(
        tiny_diffusion_backend):  # noqa: F811
    system = _system(tiny_diffusion_backend)
    chain, hit = ServingEngine(system, max_batch=SLOTS).run(
        _timed([(0.0, MISS), (0.0, HIT)]), step_level=True,
        slot_capacity=SLOTS)
    assert chain.result.steps > 0 and hit.result.steps == 0
    assert chain.release_wait == 0.0         # nothing ahead of it
    # the hit was ready at the end of its admission pass, and held while
    # the chain ahead of it ran its steps and was finalized
    assert hit.release_wait > 0.0
    assert hit.release_wait < hit.finished_at - hit.queue_delay


def test_release_wait_is_zero_in_the_group_path():
    system, _, _, _ = build_system(n_nodes=2, corpus_n=60,
                                   capacity_per_node=60, seed=0)
    reqs = list(RequestTrace(seed=2).generate(8))
    done = ServingEngine(system, max_batch=4).run(
        _timed([(0.01 * i, r.prompt) for i, r in enumerate(reqs)]))
    assert len(done) == 8
    assert all(c.release_wait == 0.0 for c in done)


def test_span_records_attributes_set_at_its_end(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("maintain", rows=7) as sp:
            sp.set_metadata(evicted=2)
    finally:
        jax.profiler.stop_trace()
    (name, _, _, stats), = _load_spans(str(tmp_path))
    assert name == "maintain" and stats == {"rows": 7, "evicted": 2}
