#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload dit_b2.reuse --seed 7 \\
        --seconds 30 --trace 0

The cell (configuration, traffic mix, chips) comes from ``BENCHMARK.json``
at the root of the checkout; the configuration, the traffic mix, the
cell's limits and each metric's reader are files of their own under
``perfbench/``, found by name.  Weights, corpus, fleet rows and traffic
are all made from ``--seed``.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and the benchmark's own spans.  Either way the window's sampled
outputs are compared with the plain reference afterwards; every compared
number is printed beside its limit as the last lines on stderr and under
``checks``, the last key of the result line.  ``--control 1`` puts the
lower-precision control in the program's place for that comparison, so
the run has to come out not correct (for setting and proving the limits;
the benchmark's own runs leave it off).

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

CACHE_DIR = os.path.join(ROOT, ".cache", "perfbench_jax")
TRACE_DIR = os.path.join(ROOT, ".cache", "perfbench_trace")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics
    without a trace, its per-layer metrics with one."""
    reported = [m for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])]
    if not trace:
        return reported
    names = {m["name"] for m in reported}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in names]


def reader(name: str):
    """The ``read`` function of the metric ``name``: from
    ``metrics/<name>.py``, or where a metric is split by cell
    (``mfu.reuse``) and has no file of its own, from the file of the name
    before its last dot (``metrics/mfu.py``)."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, "metrics", f"{name.rsplit('.', 1)[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class RunData:
    """What the metric readers read: the window's completions on the
    engine clock, the benchmark's spans and counters, the trace
    reduction (traced runs only), work counts and the chip's peaks."""

    def __init__(self, *, cfg, setup_s, window, rec, red, peak, rows0):
        self.cfg, self.setup_s = cfg, setup_s
        self.window, self.rec, self.red, self.peak = window, rec, red, peak
        self.rows0 = rows0
        done = window.done
        self.latency = np.array([c.finished_at - c.request.submitted_at
                                 for c in done])
        self.queue_delay = np.array([c.queue_delay for c in done])
        # a hit: 0 steps, and not coalesced onto an in-flight generation
        self.hit = np.array([c.result.steps == 0
                             and (rec.request(c.result) or ("",))[0]
                             != "alias" for c in done], bool)
        self.finished = max((c.finished_at for c in done), default=0.0)


def _finite(v: float) -> float:
    """JSON has no infinity: an unbounded gap prints as 1e300."""
    return float(v) if np.isfinite(v) else 1e300


def result_device(dev, count: int, peak_bytes: int, red) -> dict:
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": count, "memory_peak_bytes": int(peak_bytes)}
    if red is not None:
        out["busy_s"] = red.busy_s
        out["window_s"] = red.window_s
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; cells: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    import traffic
    spec = traffic.load(cell["traffic"])
    limits = load_json(os.path.join(HERE, "cells",
                                    f"{args.workload}.json"))["limits"]
    metrics = cell_metrics(bench, args.workload, bool(args.trace))
    readers = {m["name"]: reader(m["name"]) for m in metrics}

    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # the TPU runtime logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".cache",
                                                      "tpu_logs"))
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 3
    return run_cell(args, cfg, spec, limits, metrics, readers, dev,
                    len(devices))


def run_cell(args, cfg, spec, limits, metrics, readers, dev, count,
             *, use_pallas: Optional[bool] = True) -> int:
    """Set-up, window, trace reduction, check and result line of one run
    (``main`` after the device gate)."""
    import bench as B
    import check
    import tracereduce
    import work

    peak = work.peaks(dev.device_kind)
    counter = B.CompileCounter()
    t_build = time.perf_counter()
    setup = B.build(cfg, spec, args.seed, args.seconds,
                    use_pallas=use_pallas)
    setup.marks = {"start": t_build - T_START, **setup.marks}
    rec = B.Recorder(np.random.default_rng([args.seed, 4]))
    B.instrument(setup.system, setup.backend, rec)
    rows0 = sum(db.size for db in setup.system.dbs)
    trace_dir = (os.path.join(TRACE_DIR, args.workload) if args.trace
                 else None)
    setup_s = time.perf_counter() - T_START
    win = B.serve_window(setup, cfg, rec, counter, trace_dir=trace_dir)
    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use", 0)
    routes: Dict[str, int] = {}
    for c in win.done:
        key = c.result.fast_path or c.result.route.value
        routes[key] = routes.get(key, 0) + 1

    red = None
    if trace_dir is not None:
        red = tracereduce.reduce(tracereduce.load_events(
            tracereduce.find_xplane(trace_dir)))
    mirror = B.host_mirror(setup.system)
    weights, marks = setup.weights, setup.marks
    attempted = len(setup.window)
    setup.system = setup.backend = None
    del setup
    gc.collect()
    t_check = time.perf_counter()
    numbers = check.run(attempted=attempted, done=win.done, rec=rec,
                        progress=win.progress, weights=weights, cfg=cfg,
                        mirror=mirror, control=bool(args.control))
    check_s = time.perf_counter() - t_check

    data = RunData(cfg=cfg, setup_s=setup_s, window=win,
                   rec=rec, red=red, peak=peak, rows0=rows0)
    out_metrics: Dict[str, dict] = {}
    for m in metrics:
        v = readers[m["name"]](data)
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checks = {name: {"value": _finite(numbers[name]),
                     "limit": limits[name]} for name in check.NUMBERS}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = int(numbers["failed_requests"])
    log = sys.stderr
    print(f"compiles_in_window {win.compiles}", file=log)
    print(f"routes {json.dumps(routes, sort_keys=True)}", file=log)
    print(f"window wall_s {win.wall_s:.3f} engine_s {data.finished:.3f} "
          f"requests {len(win.done)} steps {len(win.slot_occupancy)}",
          file=log)
    print(f"setup_s {setup_s:.3f} {json.dumps(marks)}", file=log)
    print(f"reference_check_s {check_s:.3f}", file=log)
    if args.control:
        print("control: the reference one precision step below the "
              "stated one stands in the program's place for the gaps",
              file=log)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=log)
    result = {"correct": correct, "attempted": attempted,
              "failed": failed, "metrics": out_metrics,
              "device": result_device(dev, count, peak_bytes, red)}
    if red is not None:
        result["breakdown"] = tracereduce.breakdown(red)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
