"""``plan_device_ms`` against device programs worked out by hand, and the
readings of the recorded trace (``data/trace_window.json.gz``, taken
before the program named its plan steps and spatial kernels) as they
were."""
import dataclasses
from pathlib import Path

import pytest

from bench import devtrace as DT
from bench import harness as H

DATA = Path(__file__).parent / "data" / "trace_window.json.gz"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


class _Run:
    def __init__(self, trace, chunks):
        self.trace, self.peak = trace, PEAK
        self.rec = H.Served(chunks=[object()] * chunks)


@pytest.fixture(scope="module")
def recorded():
    return DT.Trace(DT.load_json(str(DATA)), devices=range(1))


def _module(plane, name, start, dur):
    return DT.Event(plane, DT.MODULES_LINE, name, start, dur)


def test_plan_device_ms_by_hand():
    tpu0, tpu1 = "/device:TPU:0", "/device:TPU:1"
    ev = [DT.Event("/host:CPU", "python", "bench.window", 0, 1000),
          _module(tpu0, "jit_plan_counts(11)", 100, 50),
          _module(tpu0, "jit_plan_spatial(12)", 200, 60),
          _module(tpu0, "jit_plan_counts(11)", 400, 30),
          _module(tpu0, "jit_plan_region_r0(13)", 980, 60),   # 20 inside
          _module(tpu0, "jit_plan_counts(11)", -40, 50),      # 10 inside
          _module(tpu0, "jit_filter_step(2)", 500, 300),
          _module(tpu0, "jit_temporal_scan(3)", 900, 10),
          _module(tpu1, "jit_plan_counts(11)", 100, 500),
          DT.Event(tpu0, DT.OPS_LINE, "jit_plan_counts", 100, 50)]
    read = H.load_reader("plan_device_ms")
    # (50 + 60 + 30 + 20 + 10) ns over 2 chunks, in ms; chip 1 left out
    got = read(_Run(DT.Trace(ev, devices=range(1)), 2))
    assert got == pytest.approx(170e-9 / 2 * 1e3)
    # both chips' programs when the cell has two
    got = read(_Run(DT.Trace(ev, devices=range(2)), 2))
    assert got == pytest.approx(670e-9 / 2 * 1e3)
    others = [e for e in ev if e.line != DT.MODULES_LINE
              or not e.name.startswith("jit_plan_")]
    assert read(_Run(DT.Trace(others, devices=range(2)), 2)) is None
    assert read(_Run(None, 2)) is None
    assert read(_Run(DT.Trace(ev, devices=range(1)), 0)) is None


def test_recorded_trace_reads_as_before(recorded):
    """The recorded window reads as it did: its plan steps were named
    ``jit_step_fn``, so ``plan_device_ms`` finds nothing there."""
    assert recorded.window_s == pytest.approx(0.018302496, rel=1e-12)
    assert recorded.busy_s() == pytest.approx(0.002554763, rel=1e-12)
    assert dict(recorded.idle_gaps()) == pytest.approx(
        {"bench.run_chunk": 0.015747733}, rel=1e-12)
    assert H.load_reader("cam_head_roofline")(_Run(recorded, 1)) == \
        pytest.approx(50.430539751654386, rel=1e-12)
    assert H.load_reader("spatial_stats_roofline")(_Run(recorded, 1)) == \
        pytest.approx(1.320641942195072, rel=1e-12)
    assert H.load_reader("plan_device_ms")(_Run(recorded, 1)) is None


def test_spatial_roofline_finds_the_named_kernel(recorded):
    """``spatial_stats_roofline`` finds the spatial kernel by its result
    shape: under the name the program now gives it (``spatial_stats``,
    vmapped over the streams) it reads what it read as ``vmap__``."""
    read = H.load_reader("spatial_stats_roofline")
    before = read(_Run(recorded, 1))
    renamed = [dataclasses.replace(
        e, name=e.name.replace("%vmap__.1 =", "%vmap_spatial_stats_.1 ="))
        for e in recorded.events]
    assert sum("%vmap_spatial_stats_.1 =" in e.name for e in renamed) == 1
    after = read(_Run(DT.Trace(renamed, devices=range(1)), 1))
    assert after == before
