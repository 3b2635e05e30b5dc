"""The reading of the program's spans: union and innermost-span idle on
synthetic events whose answers are known, and on a trace the profiler
wrote on the CPU (``testdata/cpu_spans``), which
``record_cpu_trace`` below writes afresh:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/test_programspans.py
"""
import glob
import os
import shutil
import time
import types

import numpy as np
import pytest

import programspans as ps
import run

HERE = os.path.dirname(os.path.abspath(__file__))
TESTDATA = os.path.join(HERE, "testdata", "cpu_spans")
MS = 1e6                              # ns


def _synthetic(chips=1):
    """Window 0..100 ms; on the first chip the device is busy 0-1 ms
    (a program that started before the window), 45-58 ms (inside a
    launch) and 63-66 ms (inside a decode); any other chip is busy all
    the time."""
    spans = [("maintain", -20 * MS, -5 * MS, {}),     # before the window
             ("serve.run", 0, 100 * MS, {}),
             ("serve.admit", 10 * MS, 30 * MS, {"first_req": 0, "n": 2}),
             ("stage.Plan", 15 * MS, 25 * MS, {"n": 2}),
             ("slot.step", 40 * MS, 70 * MS, {"active": 2}),
             ("slot.launch", 45 * MS, 60 * MS, {}),
             ("slot.decode", 62 * MS, 68 * MS, {"req": 0}),
             ("maintain", 80 * MS, 120 * MS, {"rows": 9})]  # past its end
    device = {"/device:TPU:0": [(-30 * MS, -25 * MS), (-3 * MS, 1 * MS),
                                (45 * MS, 58 * MS), (63 * MS, 66 * MS)]}
    for c in range(1, chips):
        device[f"/device:TPU:{c}"] = [(-5 * MS, 105 * MS)]
    return ps.Trace(window=(0.0, 100 * MS), spans=spans, device=device)


def test_covered_is_the_union_clipped_to_the_window():
    tr = _synthetic()
    assert ps.window_s(tr) == pytest.approx(0.1)
    assert ps.covered("maintain", tr) == pytest.approx(0.020)
    assert ps.covered("slot.step", tr) == pytest.approx(0.030)
    assert ps.covered("serve.run", tr) == pytest.approx(0.100)
    assert ps.covered("compile", tr) == 0.0
    # overlapping spans of one name count once
    tr.spans.append(("maintain", 85 * MS, 90 * MS, {}))
    assert ps.covered("maintain", tr) == pytest.approx(0.020)


def test_idle_goes_to_the_innermost_open_span():
    idle = ps.idle_by_span(_synthetic())
    want = {"serve.run": 29, "serve.admit": 10, "stage.Plan": 10,
            "slot.step": 9, "slot.launch": 2, "slot.decode": 3,
            "maintain": 20}
    assert set(idle) == set(want)
    for name, ms in want.items():
        assert idle[name] == pytest.approx(ms * 1e-3), name
    assert sum(idle.values()) == pytest.approx(0.1 - 0.017)


def test_idle_within_a_span_and_over_chips():
    within = ps.idle_by_span(_synthetic(), within="slot.step")
    assert within == pytest.approx({"slot.step": 9e-3, "slot.launch": 2e-3,
                                    "slot.decode": 3e-3})
    # a second chip busy all window long halves every reading
    two = ps.idle_by_span(_synthetic(chips=2))
    one = ps.idle_by_span(_synthetic())
    assert two == pytest.approx({k: v / 2 for k, v in one.items()})


def test_idle_outside_every_span_is_keyed_none():
    tr = _synthetic()
    tr.spans = [sp for sp in tr.spans if sp[0] != "serve.run"]
    idle = ps.idle_by_span(tr)
    assert idle[None] == pytest.approx(0.029)
    assert ps.idle_by_span(tr, within="serve.run") == {}


def test_no_device_plane_reads_no_idle():
    tr = _synthetic()
    tr.device = {}
    assert ps.idle_by_span(tr) is None
    assert ps.covered("maintain", tr) == pytest.approx(0.020)


# ---------------------------------------------------------------------------
# a trace the profiler wrote on the CPU
# ---------------------------------------------------------------------------

SLEEP_S = {"maintain": 0.030, "stage.Embed": 0.002, "slot.launch": 0.003}


def record_cpu_trace(log_dir):
    """Program spans of the shapes the program opens, with known sleeps,
    around and across a ``bench:window``: a sweep before the window (not
    counted), four admit/step/finalize rounds, and one sweep inside
    ``stage.Finish``."""
    import jax
    from jax.profiler import TraceAnnotation

    def span(name, **attrs):
        return TraceAnnotation(ps.PREFIX + name, **attrs)
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with span("maintain", rows=5):
        time.sleep(SLEEP_S["maintain"])
    with TraceAnnotation("bench:window"):
        with span("serve.run", requests=4):
            for req in range(4):
                with span("serve.admit", first_req=req, n=1, free=4):
                    with span("stage.Embed", n=1):
                        time.sleep(SLEEP_S["stage.Embed"])
                with span("slot.step", active=1):
                    with span("slot.launch"):
                        time.sleep(SLEEP_S["slot.launch"])
                with span("serve.finalize", req=req, release_wait=0.0):
                    with span("stage.Finish", n=1):
                        if req == 2:
                            with span("maintain", rows=7) as sp:
                                time.sleep(SLEEP_S["maintain"])
                                sp.set_metadata(evicted=0)
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return path


def _check_cpu_trace(tr):
    assert tr is not None
    names = {sp[0] for sp in tr.spans}
    assert {"serve.run", "serve.admit", "stage.Embed", "slot.step",
            "slot.launch", "serve.finalize", "stage.Finish",
            "maintain"} <= names
    sweeps = [sp for sp in tr.spans if sp[0] == "maintain"]
    assert [sp[3] for sp in sweeps] == [{"rows": 5},
                                        {"rows": 7, "evicted": 0}]
    # only the sweep inside the window counts
    assert ps.covered("maintain", tr) == pytest.approx(
        SLEEP_S["maintain"], abs=0.01)
    for name in ("stage.Embed", "slot.launch"):
        assert ps.covered(name, tr) == pytest.approx(4 * SLEEP_S[name],
                                                     abs=0.01)
    assert ps.covered("serve.run", tr) <= ps.window_s(tr)
    assert ps.idle_by_span(tr) is None       # the CPU has no device plane
    reqs = [sp[3]["req"] for sp in tr.spans if sp[0] == "serve.finalize"]
    assert reqs == [0, 1, 2, 3]


def test_committed_cpu_trace_reads():
    path, = glob.glob(os.path.join(TESTDATA, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    _check_cpu_trace(ps.load(path))


def test_fresh_cpu_trace_reads(tmp_path):
    _check_cpu_trace(ps.load(record_cpu_trace(str(tmp_path / "t"))))


def test_only_a_trace_this_process_wrote_is_read(tmp_path, monkeypatch):
    src, = glob.glob(os.path.join(TESTDATA, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    dst = tmp_path / "cell" / "plugins" / "profile" / "t" / "h.xplane.pb"
    dst.parent.mkdir(parents=True)
    shutil.copy(src, dst)
    monkeypatch.setattr(ps, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(ps, "_cache", {})
    old = ps.LOADED_AT - 3600
    os.utime(dst, (old, old))
    assert ps.trace() is None and ps.covered("maintain") is None
    monkeypatch.setattr(ps, "_cache", {})
    os.utime(dst, None)
    assert ps.covered("maintain") == pytest.approx(SLEEP_S["maintain"],
                                                   abs=0.01)


def test_a_trace_without_program_spans_reads_nothing(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench:window"):
        with TraceAnnotation("bench:maintain"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert ps.load(path) is None


# ---------------------------------------------------------------------------
# the readers, on a program that counts and one that does not
# ---------------------------------------------------------------------------


def _data(waits, hit, occupancy=(2, 2)):
    done = [types.SimpleNamespace(release_wait=w) if w is not None
            else types.SimpleNamespace() for w in waits]
    window = types.SimpleNamespace(done=done,
                                   slot_occupancy=list(occupancy))
    return types.SimpleNamespace(window=window, hit=np.array(hit, bool),
                                 red=object())


def test_hold_reads_the_hits_release_wait():
    read = run.reader("hold_p95_s")
    waits = [0.0, 3.0, 0.5, 0.2, 9.0]
    r = _data(waits, [True, False, True, True, False])
    assert read(r) == pytest.approx(np.percentile([0.0, 0.5, 0.2], 95))
    assert read(_data([None] * 5, [True] * 5)) is None     # not counted
    assert read(_data(waits, [False] * 5)) is None          # no hits


def test_span_readers_read_this_runs_trace(monkeypatch):
    tr = _synthetic()
    monkeypatch.setattr(ps, "_cache", {"run": tr})
    r = _data([0.0, 0.0], [True, True], occupancy=[2, 2, 1])
    assert run.reader("maintain_share.reuse")(r) == pytest.approx(20.0)
    assert run.reader("maintain_share.novel")(r) == pytest.approx(20.0)
    # 14 ms idle inside slot.step over 3 launches
    assert run.reader("step_idle_ms.novel")(r) == pytest.approx(14 / 3)
    monkeypatch.setattr(ps, "_cache", {"run": None})
    for name in ("maintain_share.reuse", "step_idle_ms.novel"):
        assert run.reader(name)(r) is None


if __name__ == "__main__":
    import sys
    print(record_cpu_trace(sys.argv[1] if len(sys.argv) > 1 else TESTDATA))
