"""The trace reduction against a small recorded trace: 18 ms of the
Qwen2-0.5B live cell on a TPU v5 lite (the end of a filter step with its
CAM-head kernel, the plan's group steps with the spatial kernel, the
benchmark's host spans), and against intervals worked out by hand."""
from pathlib import Path

import pytest

from bench import devtrace as DT
from bench import harness as H

DATA = Path(__file__).parent / "data" / "trace_window.json.gz"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def trace():
    return DT.Trace(DT.load_json(str(DATA)), devices=range(1))


def _busy_by_sweep(trace):
    """Busy time by a sweep over interval endpoints (an independent
    writing of the union)."""
    points = []
    for e in trace.ops():
        points += [(max(e.start_ns, trace.lo), 1),
                   (min(e.end_ns, trace.hi), -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(points, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy / 1e9


def test_window_busy_and_idle(trace):
    assert trace.window_s == pytest.approx(0.018302496)
    assert trace.busy_s() == pytest.approx(_busy_by_sweep(trace), rel=1e-9)
    assert 0 < trace.busy_s() < trace.window_s
    idle = sum(s for _, s in trace.idle_gaps(100))
    assert idle == pytest.approx(trace.window_s - trace.busy_s(), rel=1e-9)
    assert {n for n, _ in trace.idle_gaps()} <= {
        "bench.run_chunk", "bench.fetch_wait", "bench.filter_dispatch",
        "none"}


def test_modules_and_self_time(trace):
    filt = [e for e in trace.events if e.line == DT.MODULES_LINE
            and DT.module_base(e.name) == "jit_filter_step"]
    assert len(filt) == 1
    want = (min(filt[0].end_ns, trace.hi) - max(filt[0].start_ns,
                                                trace.lo)) / 1e9
    assert trace.module_s("jit_filter_step") == pytest.approx(want)
    assert trace.module_count("jit_step_fn") == 3
    top = trace.top_ops(1000)
    assert all(t >= -1e-12 for _, t in top)
    total = sum(min(e.end_ns, trace.hi) - max(e.start_ns, trace.lo)
                for e in trace.ops()) / 1e9
    assert sum(t for _, t in top) <= total + 1e-12
    assert any(k.startswith("jit_filter_step:") for k, _ in top)


class _Run:
    def __init__(self, trace):
        self.trace, self.peak = trace, PEAK


def test_kernel_rooflines(trace):
    cam = [e for e in trace.ops() if DT.op_name(e.name).startswith("cam_head")]
    stats = [e for e in trace.ops() if DT.op_name(e.name).startswith("vmap")]
    assert len(cam) == 1 and len(stats) == 1
    # CAM head over (8, 3136, 256) f32 features, 8 classes: it must move
    # 4 * (8*3136*256 + 256*8 + 8 + 8*3136*8 + 8*8) = 26,501,408 bytes
    want = 26_501_408 / 819e9 / (cam[0].dur_ns / 1e9) * 100
    got = H.load_reader("cam_head_roofline")(_Run(trace))
    assert got == pytest.approx(want)
    # spatial statistics over (4 streams, 8 frames, 3136 cells, 5
    # classes): 4 * (32*3136*5 + 32*5*5) = 2,010,240 bytes
    want = 2_010_240 / 819e9 / (stats[0].dur_ns / 1e9) * 100
    got = H.load_reader("spatial_stats_roofline")(_Run(trace))
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_union_by_hand():
    ev = [DT.Event("/host:CPU", "python", "bench.window", 0, 100),
          DT.Event("/device:TPU:0", "XLA Ops", "a", 10, 20),
          DT.Event("/device:TPU:0", "XLA Ops", "b", 15, 10),
          DT.Event("/device:TPU:0", "XLA Ops", "c", 50, 60),
          DT.Event("/host:CPU", "python", "bench.fetch_wait", 30, 15)]
    tr = DT.Trace(ev)
    # busy [10, 30) and [50, 100): 70 ns; idle [0,10) none, [30,50) wait
    assert tr.busy_s() == pytest.approx(70e-9)
    assert dict(tr.idle_gaps()) == pytest.approx(
        {"none": 10e-9, "bench.fetch_wait": 20e-9})
    assert dict(tr.top_ops()) == pytest.approx(
        {":a": 10e-9, ":b": 10e-9, ":c": 50e-9})


def test_from_profile_keeps_device_events_and_both_host_spans():
    """A profile's device events, the benchmark's ``bench.`` spans and the
    program's ``repro.`` spans are kept; other host events are not."""
    from types import SimpleNamespace as NS

    def plane(name, line, *events):
        return NS(name=name, lines=[NS(name=line, events=[
            NS(name=n, start_ns=s, duration_ns=d) for n, s, d in events])])

    pd = NS(planes=[
        plane("/device:TPU:0", "XLA Ops", ("%fusion.1 = f32[8]", 5, 3)),
        plane("/host:CPU", "python", ("bench.window", 0, 100),
              ("repro.engine.run_chunk", 10, 20), ("PjitFunction", 12, 1),
              ("bench.fetch_wait", 40, 5))])
    ev = DT.from_profile(pd)
    assert [e.name for e in ev] == ["%fusion.1 = f32[8]", "bench.window",
                                    "repro.engine.run_chunk",
                                    "bench.fetch_wait"]
    tr = DT.Trace(ev)
    assert [e.name for e in tr.host] == ["bench.fetch_wait"]
    assert [e.name for e in tr.program] == ["repro.engine.run_chunk"]
