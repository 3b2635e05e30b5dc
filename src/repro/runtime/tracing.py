"""Program spans on the device trace's clock.

A span is a ``jax.profiler.TraceAnnotation`` named ``cg:<name>``.  While
the profiler records, it lands in the same ``.xplane.pb`` as the device's
programs, so every device-idle gap has a host cause; while it does not,
a span costs about a microsecond and records nothing.  The profiler holds
the spans in memory and writes them at ``stop_trace``: there is no other
recorder and no switch.

Counts ride on the span where the work happens, as attributes (the
event's stats): ``rows``, ``evicted``, ``queries``, ``active``, ``n``.
A request is named by the serving engine's handle, ``req``; a span over
a batch carries ``first_req`` and ``n``.  Spans are opened per pass,
launch or request, never per slot per step or per row.

The spans, outermost first (``docs/ARCHITECTURE.md``, "Timing contract"):

* ``serve.run`` — one serving loop (``requests``);
* ``serve.admit`` — one step-level admission pass, slot seating included
  (``first_req``, ``n``, ``free``);
* ``serve.finalize`` — one request's Archive and Finish (``req``,
  ``release_wait``);
* ``stage.<Name>`` — one pipeline stage over a batch (``n``);
* ``scan`` — the fused per-node scan, launch and host merge (``queries``,
  ``rows``);
* ``maintain`` — one eviction sweep (``rows`` visited, ``evicted``);
* ``slot.seat`` — seating one chain (``req``, ``kind``);
* ``slot.step`` — one denoising step launch (``active``), with children
  ``slot.upload``, ``slot.launch``, ``slot.download`` and ``slot.decode``
  (``req``);
* ``compile`` — a served program compiled on first use (``kind``,
  ``batch``).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "cg:"


def span(name: str, **attrs) -> TraceAnnotation:
    """A ``cg:<name>`` span with ``attrs`` as its stats.  Use it as a
    context manager; counts known only at the end go in through the
    entered span's ``set_metadata``."""
    return TraceAnnotation(PREFIX + name, **attrs)
