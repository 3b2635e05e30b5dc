"""The trace reduction, on events recorded from a traced DiT-B/2 serve on
one TPU v5 lite, and on a trace the profiler writes here."""
import json
import os

import numpy as np
import pytest

import tracereduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "testdata",
                           "b2_reuse_trace_events.json")) as f:
        data = json.load(f)
    return tr.Events(
        device={k: [tuple(x) for x in v] for k, v in data["device"].items()},
        spans=[tuple(x) for x in data["spans"]])


def _busy_by_grid(ev, w0, w1, step=1000.0):
    """Busy time by counting 1 us cells covered by any program."""
    grid = np.arange(w0, w1, step)
    busy = np.zeros(grid.shape, bool)
    for _, s, d in next(iter(ev.device.values())):
        busy |= (grid >= s) & (grid < s + d)
    return busy.sum() * step * 1e-9


def test_busy_is_the_union_of_program_intervals(events):
    red = tr.reduce(events)
    (_, w0, wd), = [x for x in events.spans if x[0] == "window"]
    assert red.window_s == pytest.approx(wd * 1e-9)
    assert red.busy_s == pytest.approx(_busy_by_grid(events, w0, w0 + wd),
                                       rel=2e-3)
    assert 0.0 < red.busy_s < red.window_s
    assert red.chips == 1


def test_programs_take_the_label_of_the_span_that_launched_them(events):
    red = tr.reduce(events)
    labels = {k.split(":", 1)[1]: k.split(":", 1)[0] for k in red.programs}
    # the three served lambdas share a name and differ by fingerprint
    assert labels["jit__lambda(2287795510420566479)"] == "step_slots"
    assert labels["jit__lambda(14296340484316787371)"] == "slot_decode"
    # 1 ms ahead of its host span on the device clock, still attributed
    assert labels["jit__lambda(193608164302834149)"] == "slot_img_init"
    assert all(v == "scan" for k, v in labels.items()
               if k.startswith("jit_vdb_topk_pernode"))
    secs, n = tr.program_time(red, "step_slots")
    assert n == red.spans["step_slots"] == 34
    assert secs / n == pytest.approx(3.68e-3, rel=0.01)
    total = sum(s for s, _ in red.programs.values())
    assert total == pytest.approx(red.busy_s, rel=1e-6)   # no overlaps


def test_idle_gaps_are_longest_first_and_named_by_the_innermost_span(
        events):
    red = tr.reduce(events, top=5)
    secs = [g for _, g in red.gaps]
    assert secs == sorted(secs, reverse=True) and len(secs) == 5
    labels = {lab for lab, _ in red.gaps}
    assert labels <= {lab for lab, _, _ in events.spans}
    bd = tr.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 5
    assert bd["device_ops"][0][0].startswith("step_slots:")


def test_load_events_reads_spans_from_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench:window"):
        with TraceAnnotation("bench:step_slots"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tr.load_events(tr.find_xplane(str(tmp_path)))
    assert [lab for lab, _, _ in sorted(ev.spans, key=lambda s: s[1])] == [
        "window", "step_slots"]
    red = tr.reduce(ev)      # the CPU has no TPU plane: nothing is busy
    assert red.busy_s == 0.0 and not red.programs
    assert red.spans == {"window": 1, "step_slots": 1}
