"""Staged serve pipeline: stage structure, batch-first backend protocol,
vectorized composite scoring, and per-request wall-time accounting.

The batched-vs-sequential parity contract itself is pinned in
``test_batching.py``; this module covers the redesign's new surfaces.
"""
from __future__ import annotations

import sys

import numpy as np
import pytest

from repro.core.pipeline import (CallableBackend, GenerationBackend,
                                 ServePipeline)
from repro.core.policy import GenerationPolicy
from repro.core.trace import RequestTrace, TimedRequest
from repro.launch.serve import build_system
from repro.runtime.serving import ServingEngine


# ---------------------------------------------------------------------------
# pipeline structure
# ---------------------------------------------------------------------------


def test_default_stage_names_and_order():
    assert ServePipeline().stage_names == [
        "Embed", "Schedule", "Retrieve", "Score", "Plan", "Generate",
        "Archive", "Finish"]


def test_serve_is_a_batch_of_one(monkeypatch):
    """``CacheGenius.serve`` must be a thin wrapper over ``serve_batch`` —
    no duplicated sequential routing path."""
    system, _, _, _ = build_system(n_nodes=2, corpus_n=60,
                                   capacity_per_node=60, seed=0)
    seen = {}
    orig = system.serve_batch

    def spy(prompts, *, seeds=None, quality_tiers=None):
        seen["args"] = (list(prompts), seeds, quality_tiers)
        return orig(prompts, seeds=seeds, quality_tiers=quality_tiers)

    monkeypatch.setattr(system, "serve_batch", spy)
    res = system.serve("a small red circle", seed=3, quality_tier=True)
    assert seen["args"] == (["a small red circle"], [3], [True])
    assert res.image is not None


def test_request_states_carry_typed_plans():
    """Every request leaving the pipeline has a typed RequestState with a
    Plan of a known kind and a result."""
    system, _, _, _ = build_system(n_nodes=2, corpus_n=80,
                                   capacity_per_node=80, seed=0)
    reqs = list(RequestTrace(seed=1).generate(16))
    states = system.pipeline.run(
        system, [r.prompt for r in reqs], seeds=list(range(16)),
        quality_tiers=[r.quality_tier for r in reqs])
    assert [s.index for s in states] == list(range(16))
    for s in states:
        assert s.pvec is not None and s.decision is not None
        assert s.plan is not None
        assert s.plan.kind in ("alias", "history", "cached", "gen")
        assert s.result is not None and s.result.image is not None
        if s.plan.kind == "alias":
            assert 0 <= s.plan.target < s.index


# ---------------------------------------------------------------------------
# vectorized composite scoring (acceptance: no per-candidate Python calls)
# ---------------------------------------------------------------------------


def _count_scalar_score_calls(system):
    calls = {"clip": 0, "pick": 0}
    emb = system.embedder
    orig_clip, orig_pick = emb.clip_score, emb.pick_score

    def clip(*a, **k):
        calls["clip"] += 1
        return orig_clip(*a, **k)

    def pick(*a, **k):
        calls["pick"] += 1
        return orig_pick(*a, **k)

    emb.clip_score, emb.pick_score = clip, pick
    return calls


def test_serve_path_issues_no_per_candidate_score_calls():
    system, _, _, _ = build_system(n_nodes=2, corpus_n=100,
                                   capacity_per_node=100, seed=0)
    calls = _count_scalar_score_calls(system)
    reqs = list(RequestTrace(seed=1).generate(32))
    for i in range(0, 32, 8):
        chunk = reqs[i:i + 8]
        system.serve_batch([r.prompt for r in chunk],
                           seeds=list(range(i, i + len(chunk))),
                           quality_tiers=[r.quality_tier for r in chunk])
    # retrieval-scored routes actually happened...
    assert system.stats.requests == 32
    assert max(system.stats.scores) > 0
    # ...yet composite scoring never dropped to scalar Python calls
    assert calls == {"clip": 0, "pick": 0}


def test_sequential_serve_also_vectorized():
    system, _, _, _ = build_system(n_nodes=2, corpus_n=80,
                                   capacity_per_node=80, seed=0)
    calls = _count_scalar_score_calls(system)
    for i, r in enumerate(RequestTrace(seed=2).generate(12)):
        system.serve(r.prompt, seed=i)
    assert calls == {"clip": 0, "pick": 0}


def test_score_candidates_matches_scalar_scores(embedder, corpus):
    images, captions, _ = corpus
    ivecs = embedder.embed_image(images[:24])
    pvec = embedder.embed_text([captions[0]])[0]
    clips, picks = embedder.score_candidates(pvec, ivecs)
    for k in range(24):
        assert clips[k] == pytest.approx(
            embedder.clip_score(pvec, ivecs[k]), abs=1e-6)
        assert picks[k] == pytest.approx(
            embedder.pick_score(pvec, ivecs[k]), abs=1e-6)
    comp = GenerationPolicy().composite_scores(clips, picks)
    assert comp.shape == (24,)
    assert np.all((comp >= 0.0) & (comp <= 1.0))


def test_coalesced_requests_are_never_scored():
    """In-flight duplicates that alias onto an earlier batch member must
    not pay for candidate scoring (the Plan walk evaluates the lazy Score
    thunk only on the routes that read it).  Centroid mode — score-aware
    routing necessarily scores every request at schedule time (that IS
    its routing input); its call-count contract is pinned in
    ``tests/test_scheduling_score.py``."""
    system, _, _, _ = build_system(n_nodes=2, corpus_n=80,
                                   capacity_per_node=80, seed=0,
                                   routing="centroid")
    calls = {"n": 0}
    orig = system.embedder.score_candidates

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    system.embedder.score_candidates = counting
    reqs = list(RequestTrace(seed=1).generate(40))
    states = []
    for i in range(0, 40, 8):
        chunk = reqs[i:i + 8]
        states.extend(system.pipeline.run(
            system, [r.prompt for r in chunk],
            seeds=list(range(i, i + len(chunk))),
            quality_tiers=[r.quality_tier for r in chunk]))
    scored = sum(1 for s in states
                 if s.plan.kind in ("cached", "gen") and s.plan.fast is None)
    skipped = len(states) - scored
    assert skipped > 0                  # the Zipf trace produces duplicates
    assert calls["n"] == scored         # and none of them were scored


def test_score_stage_falls_back_for_embedders_without_vectorized_entry():
    """Custom embedders lacking ``score_candidates`` still serve (per-
    candidate fallback), with identical routing."""

    class _NoVectorized:
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            if name == "score_candidates":
                raise AttributeError(name)
            return getattr(self._inner, name)

    def run(wrap):
        system, _, _, _ = build_system(n_nodes=2, corpus_n=80,
                                       capacity_per_node=80, seed=0)
        if wrap:
            system.embedder = _NoVectorized(system.embedder)
        reqs = list(RequestTrace(seed=4).generate(20))
        out = system.serve_batch([r.prompt for r in reqs],
                                 seeds=list(range(20)))
        return system, out

    s_vec, r_vec = run(False)
    s_fal, r_fal = run(True)
    for a, b in zip(r_vec, r_fal):
        assert (a.fast_path or a.route.value) == (b.fast_path or b.route.value)
        assert a.node == b.node
        assert a.score == pytest.approx(b.score, abs=1e-6)
    assert s_vec.stats.route_counts == s_fal.stats.route_counts


# ---------------------------------------------------------------------------
# batch-first GenerationBackend protocol
# ---------------------------------------------------------------------------


class _BatchOnlyBackend(GenerationBackend):
    """New-style backend: only the required batched surface implemented."""

    def txt2img_batch(self, prompts, steps, seeds):
        return np.stack([np.full((4, 4, 3), float(s), np.float32)
                         for s in seeds])

    def img2img_batch(self, prompts, references, steps, seeds):
        return np.asarray(references, np.float32) * 0.5


def test_scalar_entry_points_derive_from_batch():
    b = _BatchOnlyBackend()
    img = b.txt2img("x", 5, 3)
    assert img.shape == (4, 4, 3)
    np.testing.assert_array_equal(img, np.full((4, 4, 3), 3.0, np.float32))
    ref = np.ones((4, 4, 3), np.float32)
    np.testing.assert_array_equal(b.img2img("x", ref, 5, 0), ref * 0.5)


def test_scalar_only_subclass_batches_via_loop():
    """A migrating subclass that overrides ONLY the old scalar surface
    still serves: the batched entry points loop over it."""

    class _ScalarOnly(GenerationBackend):
        def txt2img(self, prompt, steps, seed):
            return np.full((2, 2, 3), float(seed), np.float32)

        def img2img(self, prompt, reference, steps, seed):
            return np.asarray(reference) + 1.0

    b = _ScalarOnly()
    out = b.txt2img_batch(["a", "b"], 4, [1, 2])
    assert out.shape == (2, 2, 2, 3)
    np.testing.assert_array_equal(out[1], np.full((2, 2, 3), 2.0))
    refs = np.zeros((2, 2, 2, 3), np.float32)
    np.testing.assert_array_equal(b.img2img_batch(["a", "b"], refs, 4,
                                                  [0, 0]), refs + 1.0)


def test_base_protocol_requires_batched_surface():
    with pytest.raises(NotImplementedError):
        GenerationBackend().txt2img_batch(["p"], 2, [0])
    with pytest.raises(NotImplementedError):
        GenerationBackend().img2img_batch(["p"], np.zeros((1, 2, 2, 3)), 2,
                                          [0])


def test_legacy_callable_adapter_scalar_only():
    """Pre-redesign dataclass form: scalar callables only — the adapter
    derives the batched surface as a per-request loop."""
    order = []

    def t2i(prompt, steps, seed):
        order.append(prompt)
        return np.full((2, 2, 3), float(seed), np.float32)

    def i2i(prompt, ref, steps, seed):
        return np.asarray(ref) + 1.0

    for ctor in (GenerationBackend, CallableBackend):
        order.clear()
        b = ctor(txt2img=t2i, img2img=i2i)
        out = b.txt2img_batch(["a", "b"], 4, [1, 2])
        assert out.shape == (2, 2, 2, 3) and order == ["a", "b"]
        np.testing.assert_array_equal(out[0], np.full((2, 2, 3), 1.0))
        np.testing.assert_array_equal(out[1], np.full((2, 2, 3), 2.0))
        refs = np.zeros((2, 2, 2, 3), np.float32)
        np.testing.assert_array_equal(
            b.img2img_batch(["a", "b"], refs, 4, [0, 0]), refs + 1.0)
        np.testing.assert_array_equal(b.txt2img("c", 1, 7),
                                      np.full((2, 2, 3), 7.0))
        np.testing.assert_array_equal(b.img2img("c", refs[0], 1, 7),
                                      refs[0] + 1.0)


def test_legacy_callable_adapter_prefers_batch_callables():
    def t2i(prompt, steps, seed):      # pragma: no cover - must not run
        raise AssertionError("scalar callable used on the batched path")

    def t2i_batch(prompts, steps, seeds):
        return np.zeros((len(prompts), 2, 2, 3), np.float32)

    b = GenerationBackend(txt2img=t2i, img2img=None, txt2img_batch=t2i_batch)
    assert b.txt2img_batch(["a", "b", "c"], 2, [0, 1, 2]).shape == (3, 2, 2, 3)


def test_diffusion_backend_is_a_generation_backend():
    from repro.runtime.serving import DiffusionBackend
    assert issubclass(DiffusionBackend, GenerationBackend)


# ---------------------------------------------------------------------------
# per-request wall time: wall_total is the sum of the request's stage walls
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("step_level", [False, True],
                         ids=["drain", "step_level"])
def test_wall_total_is_the_sum_of_stage_walls(step_level):
    """Every result's ``wall_total`` (admission to Finish) is the sum of
    its own ``stage_walls``, over every stage in order, whether the queue
    is drained in micro-batches or served step-level."""
    system, _, _, _ = build_system(n_nodes=2, corpus_n=60,
                                   capacity_per_node=60, seed=0)
    engine = ServingEngine(system, max_batch=4)
    reqs = list(RequestTrace(seed=2).generate(10))
    if step_level:
        arrivals = [TimedRequest(0.01 * i, r.prompt, seed=i,
                                 quality_tier=r.quality_tier)
                    for i, r in enumerate(reqs)]
        done = engine.run(arrivals, step_level=True, slot_capacity=4)
    else:
        for i, r in enumerate(reqs):
            engine.submit(r.prompt, seed=i)
        done = engine.drain()
    assert len(done) == 10
    names = system.pipeline.stage_names
    for c in done:
        r = c.result
        assert list(r.stage_walls) == names
        assert all(w >= 0.0 for w in r.stage_walls.values())
        assert r.wall_total > 0.0
        assert sum(r.stage_walls.values()) == pytest.approx(r.wall_total,
                                                            rel=1e-9)


# ---------------------------------------------------------------------------
# serve CLI: --max-batch / --batch flags
# ---------------------------------------------------------------------------


def test_serve_cli_max_batch_flag(capsys):
    from repro.launch import serve as serve_cli
    argv = sys.argv
    try:
        sys.argv = ["serve", "--requests", "24", "--nodes", "2",
                    "--max-batch", "1"]
        assert serve_cli.main() == 0
        seq = capsys.readouterr().out
        sys.argv = ["serve", "--requests", "24", "--nodes", "2",
                    "--batch", "6"]
        assert serve_cli.main() == 0
        bat = capsys.readouterr().out
    finally:
        sys.argv = argv
    assert "wall latency" in seq and "max_batch=1" in seq
    assert "max_batch=6" in bat

    def grab(out, key):
        line = next(ln for ln in out.splitlines() if ln.startswith(key))
        return line.split(":", 1)[1]

    # batch=1 reproduces the sequential routing numbers exactly
    assert grab(seq, "route mix") == grab(bat, "route mix")
    assert grab(seq, "hit rate") == grab(bat, "hit rate")
    assert grab(seq, "mean latency") == grab(bat, "mean latency")
