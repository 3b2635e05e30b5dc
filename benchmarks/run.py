"""Benchmark driver: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run              # everything
    PYTHONPATH=src python -m benchmarks.run --only table1_quality

Prints ``name,seconds,key=value...`` CSV lines and writes the full JSON to
``experiments/results.json``.  The roofline tables are assembled from the
dry-run artifacts when present (``--with-roofline``).
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback


def _summarize(name: str, result: dict, secs: float) -> str:
    keys = []
    for k, v in result.items():
        if isinstance(v, bool):
            keys.append(f"{k}={v}")
        elif isinstance(v, (int, float)):
            keys.append(f"{k}={v:.4g}")
    return f"{name},{secs:.1f}s," + ",".join(keys[:6])


def _batch_sizes(text: str):
    try:
        sizes = tuple(int(b) for b in text.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected comma-separated ints (e.g. 1,4,8), got {text!r}")
    if not sizes or any(b < 1 for b in sizes):
        raise argparse.ArgumentTypeError("batch sizes must be >= 1")
    return sizes


def _arrival_rates(text: str):
    try:
        rates = tuple(float(r) for r in text.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected comma-separated floats (e.g. 10,40,160), got {text!r}")
    if not rates or any(r <= 0 for r in rates):
        raise argparse.ArgumentTypeError("arrival rates must be > 0")
    return rates


def _hit_rates(text: str):
    try:
        rates = tuple(float(r) for r in text.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected comma-separated floats (e.g. 0.2,0.5,0.8), got {text!r}")
    if not rates or any(not 0.0 <= r <= 1.0 for r in rates):
        raise argparse.ArgumentTypeError("hit rates must be in [0, 1]")
    return rates


def _pos_ints(text: str):
    try:
        vals = tuple(int(v) for v in text.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected comma-separated ints (e.g. 2,4,8), got {text!r}")
    if not vals or any(v < 1 for v in vals):
        raise argparse.ArgumentTypeError("values must be >= 1")
    return vals


def _tier_names(text: str):
    names = tuple(t.strip() for t in text.split(","))
    known = {"premium", "standard", "batch"}
    if not names or any(n not in known for n in names):
        raise argparse.ArgumentTypeError(
            f"tiers must be drawn from {sorted(known)}, got {text!r}")
    return names


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only these benchmarks (comma-separated); "
                         "their entries refresh in place, the rest of "
                         "the results file is preserved")
    ap.add_argument("--out", default="experiments/results.json")
    ap.add_argument("--with-roofline", action="store_true")
    ap.add_argument("--batch-sizes", type=_batch_sizes, default=None,
                    help="comma-separated micro-batch sizes for the "
                         "serving-throughput benchmark (default: 1,4,8)")
    ap.add_argument("--arrival-rates", type=_arrival_rates, default=None,
                    help="comma-separated offered loads (req/s) for the "
                         "serving latency-vs-load curve, the "
                         "scheduling_quality routing comparison, and the "
                         "frontdoor_load paced phase (wall req/s there) "
                         "(default: 10,40,160)")
    ap.add_argument("--hit-rates", type=_hit_rates, default=None,
                    help="comma-separated target cache hit-rates "
                         "(band-mutation fractions) for the "
                         "latent_depth_cache benchmark (default: "
                         "0.2,0.5,0.8)")
    ap.add_argument("--nodes", type=_pos_ints, default=None,
                    help="comma-separated fleet sizes for the retrieval_scan "
                         "benchmark (default: 2,4,8)")
    ap.add_argument("--cache-capacities", type=_pos_ints, default=None,
                    help="comma-separated per-node cache capacities for the "
                         "retrieval_scan benchmark (default: 2048,4096)")
    ap.add_argument("--mesh-nodes", type=_pos_ints, default=None,
                    help="comma-separated device-mesh sizes for "
                         "retrieval_scan's sharded arm (sizes > 1 shard "
                         "the cluster slabs over that many devices and "
                         "gate bitwise parity + per-device byte "
                         "shrinkage); host devices are forced "
                         "automatically on CPU (default: 1 = unsharded "
                         "only)")
    ap.add_argument("--tenants", type=_pos_ints, default=None,
                    help="comma-separated tenant counts for the "
                         "frontdoor_load contention sweep (default: 3)")
    ap.add_argument("--tiers", type=_tier_names, default=None,
                    help="comma-separated SLA tiers cycled across the "
                         "frontdoor_load paced tenants "
                         "(default: premium,standard,batch)")
    ap.add_argument("--crash-at", type=float, default=None,
                    help="fault_recovery: fraction of the trace served "
                         "before the victim node crashes (default: 0.5)")
    ap.add_argument("--corrupt-frac", type=float, default=None,
                    help="fault_recovery: fraction of the blob store the "
                         "corruption phase damages (default: 0.25)")
    ap.add_argument("--step-level", action="store_true",
                    help="extend serving_latency_curve's step-level "
                         "continuous-batching arm (ragged slot admission) "
                         "to the whole per-rate Poisson sweep; the bursty "
                         "step-level arm always runs")
    args = ap.parse_args()
    if args.crash_at is not None and not 0.0 < args.crash_at < 1.0:
        ap.error("--crash-at must be in (0, 1)")
    if args.corrupt_frac is not None and not 0.0 < args.corrupt_frac <= 1.0:
        ap.error("--corrupt-frac must be in (0, 1]")
    from repro.launch.mesh import enable_compile_cache, ensure_host_devices
    if args.mesh_nodes and max(args.mesh_nodes) > 1:
        # must land before the benchmark imports below can initialise
        # the XLA backend — host-device forcing is a no-op afterwards
        ensure_host_devices(max(args.mesh_nodes))
        import jax
        if len(jax.devices()) < max(args.mesh_nodes):
            ap.error(f"--mesh-nodes {max(args.mesh_nodes)} needs that many "
                     f"devices, backend has {len(jax.devices())}")
    enable_compile_cache()

    from benchmarks.paper_figures import ALL_BENCHMARKS, STACK_FREE
    from benchmarks import common as C

    if args.batch_sizes:
        C.BATCH_SIZES = args.batch_sizes
    if args.arrival_rates:
        C.ARRIVAL_RATES = args.arrival_rates
    if args.hit_rates:
        C.HIT_RATES = args.hit_rates
    if args.nodes:
        C.NODE_COUNTS = args.nodes
    if args.cache_capacities:
        C.CACHE_CAPACITIES = args.cache_capacities
    if args.tenants:
        C.TENANT_COUNTS = args.tenants
    if args.tiers:
        C.TIER_NAMES = args.tiers
    if args.crash_at is not None:
        C.CRASH_AT = args.crash_at
    if args.corrupt_frac is not None:
        C.CORRUPT_FRAC = args.corrupt_frac
    if args.step_level:
        C.STEP_LEVEL = True
    if args.mesh_nodes:
        C.MESH_NODES = args.mesh_nodes

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    t0 = time.time()
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in ALL_BENCHMARKS]
        if unknown:
            ap.error(f"unknown benchmark(s) {unknown}; "
                     f"choose from {sorted(ALL_BENCHMARKS)}")
    else:
        names = list(ALL_BENCHMARKS)
    results = {}
    if args.only and os.path.exists(args.out):
        # a selective run refreshes its entries in place instead of
        # wiping the rest of the results trajectory
        try:
            with open(args.out) as f:
                results = json.load(f)
        except (OSError, ValueError):
            results = {}
    if not all(n in STACK_FREE for n in names):
        print("# training/loading the reproduction stack ...")
        stack = C.get_stack()
        print(f"# stack ready in {time.time()-t0:.1f}s "
              f"(losses: {stack.losses})")
        results["stack_losses"] = stack.losses

    failures = []
    for name in names:
        fn = ALL_BENCHMARKS[name]
        t1 = time.time()
        try:
            res = fn()
            results[name] = res
            print(_summarize(name, res, time.time() - t1))
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            results[name] = {"error": str(e)}
            print(f"{name},FAILED,{type(e).__name__}: {e}")
            traceback.print_exc()

    if args.with_roofline:
        from benchmarks.roofline_table import load_records, summary
        recs = load_records()
        if recs:
            results["roofline_summary"] = summary(recs)
            print("roofline," +
                  json.dumps(results["roofline_summary"]["dominant_counts"]))

    # atomic write: a crash mid-dump must not truncate the results file
    # (a later --only run merges into it — a half-written file would
    # silently wipe the whole trajectory)
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, default=float)
    os.replace(tmp, args.out)
    print(f"# wrote {args.out}; total {time.time()-t0:.1f}s; "
          f"{len(failures)} failures {failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
