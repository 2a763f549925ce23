"""``bench/program_spans.py``: its reductions of the program's spans
against values worked out by hand, and a smoke-size traced run of the live
cell on the CPU that finds the program's spans and counters, read by the
per-layer metrics of the same names through the run's ``Run``."""
import time

import pytest

import bench.run as R
from bench import devtrace as DT
from bench import harness as H
from bench import program_spans as PS
from bench.tests.tiny import tiny_cell

HOST, TPU = "/host:CPU", "/device:TPU:0"


def _span(name, start, dur):
    return DT.Event(HOST, "python", name, start, dur)


def _op(start, dur):
    return DT.Event(TPU, DT.OPS_LINE, "op", start, dur)


def _chunk(t0):
    """One chunk at ``t0`` as the fleet path nests it, with the device busy
    only while the benchmark's filter step and the plan's tier run."""
    return [_span("bench.run_chunk", t0, 100),
            _span("repro.engine.run_chunk", t0 + 5, 90),
            _span("repro.engine.stack", t0 + 5, 20),
            _span("bench.fetch_wait", t0 + 5, 10),
            _span("repro.plan.tier.counts", t0 + 30, 20),
            _span("repro.sync.plan_undecided", t0 + 40, 8),
            _span("repro.plan.flush_stats", t0 + 55, 6),
            _span("repro.sync.plan_counts", t0 + 56, 2),
            _span("repro.engine.prefetch", t0 + 62, 12 + t0 // 100),
            _span("repro.sync.answer", t0 + 75, 15),
            _op(t0 + 15, 10), _op(t0 + 32, 5), _op(t0 + 95, 5)]


@pytest.fixture
def window():
    ev = [_span("bench.window", 0, 300)] + _chunk(0) + _chunk(100) \
        + _chunk(200)
    trace = DT.Trace(ev)
    return trace, [e for e in ev if e.name.startswith("repro.")]


def test_trace_keeps_program_spans_apart(window):
    trace, spans = window
    assert trace.program == spans
    assert {e.name for e in trace.host} == {"bench.run_chunk",
                                            "bench.fetch_wait"}


def test_idle_by_program_span(window):
    trace, spans = window
    # per chunk the device idles [0, 15), [25, 32) and [37, 95); the
    # innermost spans open at their middles are bench.fetch_wait (inside
    # repro.engine.stack), repro.engine.run_chunk, repro.engine.prefetch
    per_chunk = {"bench.fetch_wait": 15, "repro.engine.run_chunk": 7,
                 "repro.engine.prefetch": 58}
    got = PS.idle_by_span(trace, spans)
    assert got == pytest.approx({k: 3 * v * 1e-9
                                 for k, v in per_chunk.items()})
    # the benchmark's own attribution is untouched
    assert dict(trace.idle_gaps()) == pytest.approx(
        {"bench.fetch_wait": 45e-9, "bench.run_chunk": 195e-9})
    # per chunk 7 + 58 ns under program spans, over 3 chunks
    assert PS.engine_idle_ms(trace, spans, 3) == pytest.approx(65e-6)
    assert PS.engine_idle_ms(trace, [], 3) is None
    assert PS.engine_idle_ms(trace, spans, 0) is None


def test_host_fetches_per_chunk():
    assert PS.host_fetches_per_chunk({"chunks": 4, "host_fetches": 66}) \
        == 16.5
    assert PS.host_fetches_per_chunk({"chunks": 0, "host_fetches": 0}) \
        is None
    assert PS.host_fetches_per_chunk(None) is None


def test_prefetch_and_plan_host_time(window):
    _, spans = window
    assert PS.prefetch_block_ms(spans) == pytest.approx(13e-6)  # 12,13,14
    # tier 20 - 8 and flush 6 - 2 per chunk
    assert PS.plan_host_ms(spans) == pytest.approx(16e-6)
    assert PS.prefetch_block_ms([]) is None and PS.plan_host_ms([]) is None
    got = PS.span_ms(spans)
    assert got["repro.engine.prefetch"] == pytest.approx(13e-6)
    assert got["repro.sync.plan_undecided"] == pytest.approx(8e-6)
    assert len(got) == 8


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(H, "peaks", lambda kind, root=None: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


READERS = ("host_fetches_per_chunk", "prefetch_block_ms", "engine_idle_ms",
           "plan_host_ms")


def test_traced_live_run_reads_program_spans(cpu_peaks):
    cell = tiny_cell("qwen2-0.5b.live-detrac")
    assert set(READERS) <= {m["name"] for m in cell.per_layer}
    got = {}
    with PS.keep_run(got):
        res = R.measure(cell, 2 ** 31 + 17, 1.0, True, time.perf_counter())
    assert H.Run.__name__ == "Run"
    assert res["correct"], res["checks"]
    run = got["run"]
    out = PS.readings(run)
    # each per-layer metric of the result line is its reduction's reading
    for name in READERS:
        assert res["metrics"][name]["value"] == out[name], name
        assert H.load_reader(name)(run) == out[name]
    c = out["counters"]
    assert c == run.counters
    chunks = len(run.rec.chunks)
    assert c["chunks"] == chunks > 1 and c["steps_built"] == 0
    assert c["prefetch_hits"] + c["prefetch_misses"] == chunks
    # at least the answer, the plan's counts and the scan's 12 arrays
    assert out["host_fetches_per_chunk"] == c["host_fetches"] / chunks >= 14
    assert out["prefetch_block_ms"] > 0 and out["plan_host_ms"] > 0
    names = {e.name for e in run.trace.program}
    assert {"repro.executor.chunk", "repro.engine.run_chunk",
            "repro.sync.answer", "repro.temporal.advance"} <= names
    assert set(out["traced"]) == {"frames_per_s", "latency_p50_ms",
                                  "latency_p95_ms"}
