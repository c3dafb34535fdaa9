"""The reduction from a profiler trace to busy time, idle share, top ops
and labelled idle gaps: on hand-made events, on a small trace recorded on
a TPU v5e, and the reading of a profile the profiler writes."""
import json
import pathlib

import numpy as np
import pytest

import tinycell  # noqa: F401 - puts bench/ and src/ on the path
from harness import spans, xtrace

RECORDED = pathlib.Path(__file__).with_name("data") / "v5e_trace_slice.json"


def _busy_by_grid(intervals, lo, hi):
    """Busy ns by marking every ns of a small window: the slow, plain
    union the reduction has to agree with."""
    grid = np.zeros(int(hi - lo), bool)
    for s, e in intervals:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int(a - lo):int(b - lo)] = True
    return int(grid.sum())


def test_hand_made_events():
    events = {
        "devices": {"/device:TPU:0": {
            "modules": [["jit_a(1)", 100, 50], ["jit_b(2)", 120, 60],
                        ["jit_a(1)", 400, 100], ["jit_c(3)", 950, 100]],
            "ops": [["%_vm_call.1 = s32[8] custom-call(...)", 100, 40],
                    ["%while.3 = (s32[]) while(...)", 400, 100]]}},
        "host": [[spans.WINDOW, 50, 950],       # window 50..1000
                 ["bench.tick", 60, 700],
                 ["bench.group", 100, 320],     # 100..420
                 ["bench.launch", 110, 20],
                 ["bench.plan", 780, 100],      # 780..880
                 [spans.CLIENT, 700, 300]],     # 700..1000
    }
    s = xtrace.reduce(events)
    assert s.window_s == pytest.approx(950e-9)
    # busy: 100..180, 400..500, 950..1000 = 80 + 100 + 50 ns
    assert s.busy_s == pytest.approx(230e-9)
    assert s.idle_share == pytest.approx(1 - 230 / 950)
    # idle: 50..100 (middle in tick), 180..400 (in group, inside tick),
    # 500..950 (middle 725 in tick and client: the serving thread's wins)
    assert dict(s.idle_gaps) == {
        "bench.tick": pytest.approx(500e-9),
        "bench.group": pytest.approx(220e-9)}
    ops = dict(s.device_ops)
    assert ops["_vm_call"] == pytest.approx(40e-9)
    assert ops["while"] == pytest.approx(100e-9)


@pytest.mark.parametrize("name, label", [
    ("%_vm_call.1 = s32[8,1,1]{2,1,0} custom-call(s32[82,5] %copy.1)",
     "_vm_call"),
    ("%select_dynamic-update-slice_fusion.2 = u32[25,8] fusion(...)",
     "select_dynamic-update-slice_fusion"),
    ("jit__dispatch(2187970145073137012)", "jit__dispatch"),
    ("%copy-start = (u32[524288]) copy-start(u32[524288] %mask)",
     "copy-start"),
])
def test_op_label(name, label):
    assert xtrace.op_label(name) == label


def test_recorded_v5e_trace():
    """A slice of the trace of a traced window of
    ``bitmap16m.heavy.closed32`` on one TPU v5e, cut to a few ticks."""
    events = json.loads(RECORDED.read_text())
    s = xtrace.reduce(events)
    lo, hi = xtrace._window(events)
    mods = [(st, st + d) for _, st, d in
            events["devices"]["/device:TPU:0"]["modules"]]
    assert s.busy_s == pytest.approx(
        _busy_by_grid(mods, lo, hi) / 1e9, rel=1e-6)
    assert 0 < s.idle_share < 1
    assert s.busy_s + sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s, rel=1e-6)
    assert len(s.device_ops) <= xtrace.TOP
    assert len(s.idle_gaps) <= xtrace.TOP
    assert {n for n, _ in s.idle_gaps} <= set(spans.NAMES) | {
        "outside spans"}


def test_load_reads_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(spans.WINDOW):
        with jax.profiler.TraceAnnotation("bench.plan"):
            jnp.arange(8).sum().block_until_ready()
        with jax.profiler.TraceAnnotation("not.ours"):
            pass
    jax.profiler.stop_trace()
    events = xtrace.load(str(tmp_path))
    names = [n for n, _, _ in events["host"]]
    assert names.count(spans.WINDOW) == 1 and names.count("bench.plan") == 1
    assert "not.ours" not in names
    (win,) = [e for e in events["host"] if e[0] == spans.WINDOW]
    (plan,) = [e for e in events["host"] if e[0] == "bench.plan"]
    assert win[1] <= plan[1] and plan[1] + plan[2] <= win[1] + win[2]
