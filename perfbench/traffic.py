"""Traffic generation for the chip benchmark.

One general generator reads a traffic file (``perfbench/traffic/<name>.json``)
and turns it into prompts, per-request seeds and arrival times, all from
the run's ``--seed``.  The prompt laws are copied from the program's
``core/trace.py`` (Zipf-with-drift, band mutation) and the scene grammar
from ``data/synthetic.py``, so that the yardstick stays put when the
program's own generators change.

Arrivals are open loop.  Every seed of one traffic file gets the same
requests and the same set of inter-arrival gaps (the quantiles of an
exponential law at the file's rate), in a seed-drawn order: the scene
population and the warm corpus come from the file's ``population_seed``,
the prompt laws send each law's quota instead of independent draws, and
the run's seed changes which prompt comes when, not how much work a run
holds.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# scene grammar of the program's synthetic corpus (data/synthetic.py): the
# program parses these captions back into scenes, so the words must match
SHAPES = ("circle", "square", "triangle", "cross", "ring")
COLORS = ("red", "green", "blue", "yellow", "purple", "orange", "white",
          "cyan")
BACKGROUNDS = ("black", "gray", "navy", "olive", "maroon", "teal")
SIZES = ("small", "medium", "large")
POSITIONS = ("left", "center", "right")


@dataclass(frozen=True)
class Scene:
    shape: str
    color: str
    background: str
    size: str
    position: str


def caption(s: Scene) -> str:
    return (f"a {s.size} {s.color} {s.shape} at the {s.position} "
            f"on a {s.background} background")


def random_scene(rng: np.random.Generator) -> Scene:
    return Scene(rng.choice(SHAPES), rng.choice(COLORS),
                 rng.choice(BACKGROUNDS), rng.choice(SIZES),
                 rng.choice(POSITIONS))


def all_scenes() -> List[Scene]:
    return [Scene(sh, c, b, sz, p) for sh in SHAPES for c in COLORS
            for b in BACKGROUNDS for sz in SIZES for p in POSITIONS]


@dataclass
class Request:
    """One generated request: when it is due on the engine's clock, its
    prompt, its generation seed and whether it is quality-tier."""

    arrival_time: float
    prompt: str
    seed: int
    quality_tier: bool = False


def quota(probs: np.ndarray, n: int) -> np.ndarray:
    """Counts that sum to ``n`` and follow ``probs`` as closely as whole
    numbers can (largest remainders): the law's draws without their
    sampling noise, the same for every seed."""
    exact = probs * n
    counts = np.floor(exact).astype(np.int64)
    rest = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


def zipf_drift_prompts(n: int, rng: np.random.Generator,
                       pop: np.random.Generator, *, n_specs: int,
                       zipf_a: float, drift_every: int, repeat_rate: float,
                       quality_rate: float) -> List[tuple]:
    """``RequestTrace.generate``'s law: Zipf(``zipf_a``) popularity over
    ``n_specs`` scenes whose ranking rotates every ``drift_every``
    requests, verbatim repeats of the previous prompt at ``repeat_rate``,
    quality-tier users at ``quality_rate``.  Returns (prompt, quality).

    The scenes and their first ranking come from the traffic file's
    population (``pop``).  Each drift epoch holds the Zipf law's quota of
    each rank and the run's seed (``rng``) orders them, picks which
    prompts are repeated and which users are quality-tier: every seed
    sends the same requests, in another order."""
    specs, seen = [], set()
    while len(specs) < n_specs:
        s = random_scene(pop)
        if s not in seen:
            seen.add(s)
            specs.append(s)
    order = pop.permutation(n_specs)
    probs = np.arange(1, n_specs + 1, dtype=np.float64) ** (-zipf_a)
    probs /= probs.sum()
    n_rep = int(round(repeat_rate * n))
    base: List[str] = []
    for start in range(0, n - n_rep, drift_every):
        m = min(drift_every, n - n_rep - start)
        ranks = np.repeat(np.arange(n_specs), quota(probs, m))
        base.extend(caption(specs[order[r]]) for r in rng.permutation(ranks))
        order = np.roll(order, n_specs // 7)
    after = set(rng.choice(len(base), size=n_rep, replace=False).tolist())
    prompts: List[str] = []
    for i, p in enumerate(base):
        prompts.append(p)
        if i in after:
            prompts.append(p)
    quality = np.zeros(n, bool)
    quality[rng.choice(n, size=int(round(quality_rate * n)),
                       replace=False)] = True
    return list(zip(prompts, quality.tolist()))


def novel_prompts(n: int, rng: np.random.Generator,
                  pop: np.random.Generator, *,
                  band_fraction: float) -> List[tuple]:
    """``band_mutation_trace``'s law: scenes never requested before in
    the run, the first ``n`` of the population's permutation of the whole
    scene pool in a seed-drawn order, or with probability
    ``band_fraction`` a colour swap of an earlier one."""
    pool = all_scenes()
    if n > len(pool) and band_fraction == 0.0:
        raise ValueError(f"{n} novel requests exceed the {len(pool)}-scene "
                         f"pool")
    perm = pop.permutation(len(pool))[:n]
    perm = perm[rng.permutation(len(perm))]
    bases, out, nxt = [], [], 0
    for _ in range(n):
        if bases and rng.random() < band_fraction:
            b = bases[int(rng.integers(len(bases)))]
            colors = [c for c in COLORS if c != b.color]
            out.append((caption(Scene(b.shape,
                                      colors[int(rng.integers(len(colors)))],
                                      b.background, b.size, b.position)),
                        False))
        else:
            b = pool[perm[nxt % len(perm)]]
            nxt += 1
            bases.append(b)
            out.append((caption(b), False))
    return out


PROMPT_LAWS = {"zipf_drift": zipf_drift_prompts, "novel": novel_prompts}


def poisson_times(n: int, rate: float, rng: np.random.Generator,
                  ) -> np.ndarray:
    """Open-loop Poisson arrivals (``core/trace.py::poisson_arrivals``'s
    law) with every seed given the same ``n`` gaps: the quantiles of the
    exponential law at ``rate``, in a seed-drawn order."""
    q = (np.arange(n) + 0.5) / n
    return np.cumsum(rng.permutation(-np.log1p(-q) / rate))


def bursty_times(n: int, rate: float, rng: np.random.Generator, *,
                 burst_size: int, within_burst_gap: float = 0.0,
                 ) -> np.ndarray:
    """Synchronised bursts (``core/trace.py::bursty_arrivals``'s law):
    ``burst_size`` requests land together, ``within_burst_gap`` apart,
    every ``burst_size / rate`` seconds, so the mean rate is ``rate``."""
    del rng   # the schedule is the same for every seed
    i = np.arange(n)
    return (i // burst_size) * (burst_size / rate) + (
        i % burst_size) * within_burst_gap


ARRIVAL_LAWS = {"poisson": poisson_times, "bursty": bursty_times}


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def generate(spec: dict, seed: int, seconds: float,
             ) -> Tuple[List[Request], List[Request]]:
    """``(warm_up, window)`` requests from one prompt stream: the file's
    ``warmup_requests`` first, then the window's, which arrive at
    ``rate_per_s`` over the file's ``arrival_share`` of ``seconds`` (1 if
    it names none).  A mix offered above what the system sustains takes
    a share below 1, so that the backlog it builds drains inside the
    window.  Each list's clock starts at 0."""
    rate = float(spec["rate_per_s"])
    n_warm = int(spec["warmup_requests"])
    n = int(round(rate * seconds * float(spec.get("arrival_share", 1.0))))
    rng = np.random.default_rng([seed, 1])
    pop = np.random.default_rng(spec["population_seed"])
    prompts = PROMPT_LAWS[spec["law"]](n_warm + n, rng, pop,
                                       **spec["law_params"])
    arrive = ARRIVAL_LAWS[spec["arrivals"]]
    kw = spec.get("arrival_params", {})
    times = np.concatenate([arrive(n_warm, rate, rng, **kw),
                            arrive(n, rate, rng, **kw)])
    # generation seeds must fit the program's int32 PRNG seeds
    base = int(np.random.default_rng([seed, 2]).integers(0, 2 ** 30))
    reqs = [Request(float(t), p, base + i, q)
            for i, ((p, q), t) in enumerate(zip(prompts, times))]
    return reqs[:n_warm], reqs[n_warm:]
