#!/usr/bin/env python3
"""Find the highest rate a cell sustains: one fresh set-up and one window
per offered rate, lowest first, on one chip.

    python3 perfbench/sweep.py --workload dit_b2.reuse --seed 5 \\
        --seconds 30 --rates 15,20,25,30

Each window is the cell's committed traffic file with only its rate
changed, built from scratch as a run of the cell builds it (weights,
corpus, fleet, warm-up), so no window inherits a cache that an earlier
one warmed.  Each line gives the offered and the served rate (requests
over the engine clock from 0 to the last result), latency p50/p95, how
much later the last fifth of the window's requests came back than the
first fifth (a backlog that grows through the window shows as a ratio
well above 1), the maintenance sweeps inside the window and the route
mix.  The cell's rate is then set by hand in its traffic file.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]

    import run
    import traffic
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = run.load_json(os.path.join(ROOT, conf["file"]))
    spec = traffic.load(cell["traffic"])

    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".cache",
                                                      "tpu_logs"))
    import jax
    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if jax.devices()[0].platform != "tpu":
        print("the sweep runs only on a TPU", file=sys.stderr)
        return 3
    import bench as B

    counter = B.CompileCounter()
    for rate in rates:
        setup = B.build(cfg, dict(spec, rate_per_s=rate), args.seed,
                        args.seconds)
        rec = B.Recorder(np.random.default_rng([args.seed, 4]))
        B.instrument(setup.system, setup.backend, rec)
        win = B.serve_window(setup, cfg, rec, counter)
        done = win.done
        n = len(done)
        lat = np.array([c.finished_at - c.request.submitted_at
                        for c in done])
        fifth = max(n // 5, 1)
        routes = {}
        for c in done:
            k = c.result.fast_path or c.result.route.value
            routes[k] = routes.get(k, 0) + 1
        print(json.dumps({
            "offered": rate, "requests": n,
            "served": n / max(c.finished_at for c in done),
            "p50_s": float(np.percentile(lat, 50)),
            "p95_s": float(np.percentile(lat, 95)),
            "late_over_early": float(lat[-fifth:].mean()
                                     / max(lat[:fifth].mean(), 1e-9)),
            "maintain_sweeps": len(rec.walls.get("maintain", ())),
            "window_wall_s": win.wall_s,
            "compiles_in_window": win.compiles,
            "routes": routes}), flush=True)
        setup.system = setup.backend = None
        del setup, win, done
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
