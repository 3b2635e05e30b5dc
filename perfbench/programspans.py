"""The program's own spans in this run's profiler trace.

The program opens ``cg:<name>`` spans (``TraceAnnotation``s, see
``src/repro/runtime/tracing.py``) at its stage, pass, launch and request
boundaries; a traced run records them in the same ``.xplane.pb`` as the
device's programs and the benchmark's ``bench:window``.  This module
reads that file once per process and answers two questions about the
window:

* :func:`covered` — for how many seconds a span of one name was open;
* :func:`idle_by_span` — the device's idle seconds, each instant given
  to the innermost program span open at it.

Only a trace this process wrote is read: a checkout of a program that
opens no ``cg:`` span, or a run without a trace, reads nothing, and the
readers built on this module then return None.  Nothing here imports
the program, since readers load before ``src`` is on the path.
"""
from __future__ import annotations

import glob
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".cache", "perfbench_trace")
PREFIX = "cg:"
WINDOW = "bench:window"
# a trace older than this module's first import was written by another
# process
LOADED_AT = time.time()


@dataclass
class Trace:
    """A run's window, program spans and device programs, in ns."""

    window: Tuple[float, float]
    spans: List[Tuple[str, float, float, dict]] = field(
        default_factory=list)       # [(name, start, end, stats)]
    device: Dict[str, List[Tuple[float, float]]] = field(
        default_factory=dict)       # chip -> [(start, end)] of programs


def load(xplane_path: str) -> Optional[Trace]:
    """The window, the ``cg:`` spans and the device's program intervals
    of one ``.xplane.pb``; None if it holds no window or no ``cg:`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    spans, device, wins = [], {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device[plane.name] = [
                (float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
                for line in plane.lines if line.name == "XLA Modules"
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(PREFIX):
                        s = float(e.start_ns)
                        spans.append((name[len(PREFIX):], s,
                                      s + float(e.duration_ns),
                                      {k: v for k, v in e.stats}))
                    elif name == WINDOW:
                        s = float(e.start_ns)
                        wins.append((s, s + float(e.duration_ns)))
    if not wins or not spans:
        return None
    return Trace(window=(min(w[0] for w in wins), max(w[1] for w in wins)),
                 spans=spans, device=device)


_cache: Dict[str, Optional[Trace]] = {}


def trace() -> Optional[Trace]:
    """This run's trace: the newest ``.xplane.pb`` under the benchmark's
    trace directory, read once; None if there is none, if this process
    did not write it, or if it holds no program span."""
    if "run" not in _cache:
        paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                          recursive=True)
        path = max(paths, key=os.path.getmtime, default=None)
        _cache["run"] = (load(path) if path is not None
                         and os.path.getmtime(path) >= LOADED_AT else None)
    return _cache["run"]


def window_s(tr: Optional[Trace] = None) -> Optional[float]:
    tr = tr if tr is not None else trace()
    return None if tr is None else (tr.window[1] - tr.window[0]) * 1e-9


def covered(name: str, tr: Optional[Trace] = None) -> Optional[float]:
    """Seconds of the window during which a ``cg:<name>`` span was open
    (the union of those spans, clipped to the window); 0 where the
    program opened spans but none of this name."""
    tr = tr if tr is not None else trace()
    if tr is None:
        return None
    w0, w1 = tr.window
    merged = tracereduce._union([(max(s, w0), min(e, w1))
                                 for n, s, e, _ in tr.spans
                                 if n == name and e > w0 and s < w1])
    return sum(e - s for s, e in merged) * 1e-9


def _innermost_segments(tr: Trace, within: Optional[str]):
    """The window cut into ``(start, end, innermost span name)`` pieces,
    keeping only the pieces during which a ``within`` span is open when
    ``within`` is given.  Outside every program span the name is
    ``None``.  Spans of one thread nest, so the innermost open span is the
    one opened last."""
    w0, w1 = tr.window
    edges = []                    # (t, 0 = close / 1 = open, span index)
    for i, (_, s, e, _) in enumerate(tr.spans):
        if e > w0 and s < w1:
            edges.append((max(s, w0), 1, -e, i))
            edges.append((min(e, w1), 0, 0.0, i))
    edges.sort()
    open_: List[int] = []
    depth = 0                     # ``within`` spans open
    out = []
    t = w0
    for te, kind, _, i in edges:
        if te > t and open_ and (within is None or depth):
            out.append((t, te, tr.spans[open_[-1]][0]))
        elif te > t and not open_ and within is None:
            out.append((t, te, None))
        t = max(t, te)
        if kind:
            open_.append(i)
            depth += tr.spans[i][0] == within
        else:
            open_.remove(i)
            depth -= tr.spans[i][0] == within
    if t < w1 and within is None:
        out.append((t, w1, None))
    return out


def idle_by_span(tr: Optional[Trace] = None, *,
                 within: Optional[str] = None,
                 ) -> Optional[Dict[Optional[str], float]]:
    """Device-idle seconds inside the window, averaged over the chips,
    keyed by the innermost program span open at each idle instant (None:
    no program span open).  With ``within``, only idle time while a
    ``cg:<within>`` span is open counts.  None without a device plane."""
    tr = tr if tr is not None else trace()
    if tr is None or not tr.device:
        return None
    w0, w1 = tr.window
    segs = _innermost_segments(tr, within)
    out: Dict[Optional[str], float] = {}
    for rows in tr.device.values():
        busy = tracereduce._union([(max(s, w0), min(e, w1))
                                   for s, e in rows if e > w0 and s < w1])
        j = 0
        for s, e, name in segs:
            # idle = the piece minus the busy intervals that overlap it
            idle = e - s
            while j < len(busy) and busy[j][1] <= s:
                j += 1
            k = j
            while k < len(busy) and busy[k][0] < e:
                idle -= min(e, busy[k][1]) - max(s, busy[k][0])
                k += 1
            if idle > 0:
                out[name] = out.get(name, 0.0) + idle
    chips = len(tr.device)
    return {k: v * 1e-9 / chips for k, v in out.items()}
