"""A whole run of a tiny cell on the CPU, past the device gate: it comes
out correct, and with the timed path broken underneath it does not.

Each fault is planted in the program where the work is produced, before
the run builds and compiles its programs: a denoising step that returns
its state unchanged, a step that leaves half of the slots out, a decoded
image altered, scan scores altered, and a route that disagrees with the
policy.  The control, put in the program's place, must come out not
correct on each of the three gaps.  Results that come back in another
order than they were submitted must change nothing.
"""
import json

import jax.numpy as jnp
import pytest

import run
import tiny


def _run(capsys, control=0, trace=0, cell="dit_b2.reuse"):
    bench = run.load_json(f"{run.ROOT}/BENCHMARK.json")
    spec = {w["name"]: w for w in bench["workloads"]}[cell]
    metrics = run.cell_metrics(bench, cell, bool(trace))
    readers = {m["name"]: run.reader(m["name"]) for m in metrics}
    rc = run.run_cell(tiny.args(control=control, trace=trace),
                      tiny.config(spec["config"]),
                      tiny.traffic(spec["traffic"]), tiny.limits(),
                      metrics, readers, tiny.device(), 1, use_pallas=False)
    assert rc == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check failed_requests")
    return result, err


@pytest.mark.parametrize("cell,trace,reported", [
    ("dit_b2.reuse", 0, {"setup_s", "image_p50_s", "image_p95_s",
                         "hit_p95_s"}),
    # the CPU has no device plane to read: those readers return nothing
    ("dit_b2.reuse", 1, {"queue_wait_p95_s", "admission_ms",
                         "finalize_ms"}),
    ("dit_l2.novel", 0, {"setup_s", "images_per_s"}),
    ("dit_l2.novel", 1, {"slot_occupancy"}),
])
def test_sound_run_is_correct_and_reports_its_metrics(capsys, cell, trace,
                                                      reported):
    result, err = _run(capsys, trace=trace, cell=cell)
    assert result["correct"], result["checks"]
    spec = tiny.traffic(cell.split(".")[1])
    offered = 1.5 * spec["rate_per_s"] * spec.get("arrival_share", 1.0)
    assert result["failed"] == 0 and result["attempted"] == round(offered)
    assert "compiles_in_window 0" in err
    assert set(result["metrics"]) == reported
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert trace == ("busy_s" in result["device"])


def test_control_in_the_programs_place_is_not_correct(capsys):
    result, err = _run(capsys, control=1)
    assert not result["correct"]
    for name in ("step_gap", "decode_gap", "scan_gap"):
        c = result["checks"][name]
        assert c["value"] > c["limit"], name
        assert f"check {name} {c['value']!r} limit" in err
    sound, _ = _run(capsys)
    for name in ("step_gap", "decode_gap", "scan_gap"):
        c = sound["checks"][name]
        assert c["value"] < c["limit"], name


def test_results_out_of_submission_order_change_nothing(capsys,
                                                         monkeypatch):
    import bench
    serve, seen = bench.serve_window, {}

    def reversed_window(setup, cfg, rec, *a, **k):
        win = serve(setup, cfg, rec, *a, **k)
        # the submission-order rule: handle h is the h-th admitted state
        kinds = rec.plan_kinds
        seen["hits"] = [c.result.steps == 0 and kinds[h] != "alias"
                        for h, c in enumerate(win.done)][::-1]
        win.done = win.done[::-1]
        return win
    datas = []

    class Kept(run.RunData):
        def __init__(self, **kw):
            super().__init__(**kw)
            datas.append(self)
    monkeypatch.setattr(bench, "serve_window", reversed_window)
    monkeypatch.setattr(run, "RunData", Kept)
    result, _ = _run(capsys)
    assert result["correct"], result["checks"]
    assert result["checks"]["decision_faults"]["value"] == 0
    hits = datas[0].hit.tolist()
    assert hits == seen["hits"] and any(hits)


def _unchanged_step(eps_fn, sched, x, ctx, t, t_prev, active, **kw):
    return x


def _half_step(eps_fn, sched, x, ctx, t, t_prev, active, **kw):
    from repro.models.diffusion.sampler import step_slots
    odd = (jnp.arange(x.shape[0]) % 2).astype(bool)
    return step_slots(eps_fn, sched, x, ctx, t, t_prev, active & ~odd)


def _plant_decode(monkeypatch):
    from repro.runtime import serving
    orig = serving.DiffusionBackend._slot_decode_core
    monkeypatch.setattr(serving.DiffusionBackend, "_slot_decode_core",
                        lambda self, v, z: orig(self, v, z).at[
                            0, 0, 0, 0].add(0.5))


def _plant_scan(monkeypatch):
    from repro.core.cluster_index import ClusterIndex
    orig = ClusterIndex._scan

    def scan(self, *a, **k):
        s, i = orig(self, *a, **k)
        return s + 1e-3, i
    monkeypatch.setattr(ClusterIndex, "_scan", scan)


def _plant_route(monkeypatch):
    from repro.core.policy import GenerationPolicy, Route
    orig = GenerationPolicy.route
    monkeypatch.setattr(
        GenerationPolicy, "route",
        lambda self, s: (Route.IMG2IMG if orig(self, s) is Route.HIT_RETURN
                         else orig(self, s)))


@pytest.mark.parametrize("fault,number", [
    ("unchanged_step", "step_gap"),
    ("half_batch", "step_gap"),
    ("decode_altered", "decode_gap"),
    ("scan_altered", "scan_gap"),
    ("route_altered", "decision_faults"),
])
def test_a_fault_in_the_timed_path_is_not_correct(capsys, monkeypatch,
                                                   fault, number):
    from repro.runtime import serving
    if fault == "unchanged_step":
        monkeypatch.setattr(serving, "step_slots", _unchanged_step)
    elif fault == "half_batch":
        monkeypatch.setattr(serving, "step_slots", _half_step)
    elif fault == "decode_altered":
        _plant_decode(monkeypatch)
    elif fault == "scan_altered":
        _plant_scan(monkeypatch)
    else:
        _plant_route(monkeypatch)
    result, _ = _run(capsys)
    assert not result["correct"]
    c = result["checks"][number]
    assert c["value"] > c["limit"]
