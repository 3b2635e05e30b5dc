"""Reduce a profiler trace of one measured window to device metrics.

Two steps, kept apart so the second can be tested on recorded events:

* :func:`load_events` reads the ``.xplane.pb`` the JAX profiler wrote and
  keeps the device's program executions (the ``XLA Modules`` line of each
  ``/device:TPU:n`` plane) and the benchmark's own host spans (events
  named ``bench:<label>`` on the host plane).
* :func:`reduce` turns those into busy time (the union of the program
  intervals, averaged over the chips), the idle share of the window,
  device time per program, and the longest idle gaps, each attributed to
  the innermost benchmark span around it.

Programs are named in the trace by their jitted function's name and a
fingerprint; several served programs are lambdas of one name.  Each
program name is therefore given the label of the benchmark span its
executions overlap most: the span the benchmark wraps around the call
that launched it.  The host and device clocks of a trace differ by up to
a few milliseconds, so overlap is counted with that much slack.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# host and device timestamps in one trace disagree by about a millisecond
SLACK_NS = 3_000_000
WINDOW = "window"


@dataclass
class Events:
    """Program executions per chip and benchmark host spans, in ns."""

    device: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)          # chip -> [(program, start, dur)]
    spans: List[Tuple[str, float, float]] = field(
        default_factory=list)          # [(label, start, dur)]


@dataclass
class Reduction:
    window_s: float
    busy_s: float                      # averaged over the chips
    programs: Dict[str, Tuple[float, int]]   # label -> (seconds, count)
    gaps: List[Tuple[str, float]]      # longest idle gaps, descending
    chips: int
    spans: Dict[str, int]              # label -> spans in the window


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_events(xplane_path: str) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    ev = Events()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            rows = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    rows.extend((e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events)
            ev.device[plane.name] = rows
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        ev.spans.append((e.name[len("bench:"):],
                                         float(e.start_ns),
                                         float(e.duration_ns)))
    return ev


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _label_programs(ev: Events) -> Dict[str, str]:
    """Program name -> label of the span its executions overlap most."""
    spans = sorted((s, s + d, lab) for lab, s, d in ev.spans
                   if lab != WINDOW)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    votes: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for rows in ev.device.values():
        for name, s, d in rows:
            e = s + d
            lo = bisect.bisect_left(starts, s - SLACK_NS - longest)
            hi = bisect.bisect_right(starts, e + SLACK_NS)
            best, best_key = "unattributed", (0.0, 0.0)
            for ss, se, lab in spans[lo:hi]:
                ov = min(e, se + SLACK_NS) - max(s, ss - SLACK_NS)
                # equal overlap: the inner (shorter) span launched it
                key = (round(ov, -3), -(se - ss))
                if ov > 0 and key > best_key:
                    best, best_key = lab, key
            votes[name][best] += d
    return {name: max(v, key=v.get) for name, v in votes.items()}


def _innermost(spans, t: float) -> str:
    best, width = WINDOW, float("inf")
    for lab, s, d in spans:
        if s <= t <= s + d and d < width and lab != WINDOW:
            best, width = lab, d
    return best


def reduce(ev: Events, *, top: int = 10) -> Reduction:
    wins = [(s, s + d) for lab, s, d in ev.spans if lab == WINDOW]
    if not wins:
        raise ValueError("the trace holds no bench:window span")
    w0, w1 = wins[0][0], wins[-1][1]
    chips = max(len(ev.device), 1)
    labels = _label_programs(ev)
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    busy_ns = 0.0
    gaps: List[Tuple[float, float]] = []
    for rows in ev.device.values():
        inside = [(max(s, w0), min(s + d, w1)) for _, s, d in rows
                  if s + d > w0 and s < w1]
        merged = _union(inside)
        busy_ns += sum(e - s for s, e in merged)
        prev = w0
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if w1 > prev:
            gaps.append((prev, w1))
        for name, s, d in rows:
            if s + d > w0 and s < w1:
                acc = programs[f"{labels[name]}:{name}"]
                acc[0] += d * 1e-9
                acc[1] += 1
    spans = [x for x in ev.spans if x[1] + x[2] > w0 and x[1] < w1]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_innermost(spans, 0.5 * (s + e)), (e - s) * 1e-9)
             for s, e in gaps[:top]]
    counts: Dict[str, int] = defaultdict(int)
    for lab, s, d in spans:
        counts[lab] += 1
    return Reduction(window_s=(w1 - w0) * 1e-9,
                     busy_s=busy_ns * 1e-9 / chips,
                     programs={k: (v[0], int(v[1]))
                               for k, v in programs.items()},
                     gaps=named, chips=chips, spans=dict(counts))


def program_time(red: Reduction, label: str) -> Tuple[float, int]:
    """Device seconds and executions of the programs launched inside the
    benchmark span ``label`` (summed over the chips)."""
    secs, n = 0.0, 0
    for key, (s, c) in red.programs.items():
        if key.split(":", 1)[0] == label:
            secs += s
            n += c
    return secs, n


def breakdown(red: Reduction, top: int = 10) -> Optional[dict]:
    ops = sorted(red.programs.items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[k, v[0]] for k, v in ops],
            "idle_gaps": [[lab, secs] for lab, secs in red.gaps[:top]]}
