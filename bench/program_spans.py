"""The program's own spans and counters over one traced window.

The fleet path opens ``repro.`` spans at each layer boundary and counts
its work in ``ShardedPlanGroupEngine.counters`` (``repro.tracing``).  The
harness keeps only the benchmark's ``bench.`` spans from a trace and no
counters, so no per-layer metric of ``BENCHMARK.json`` reads these yet.
This tool, run by hand, serves one traced window as ``bench/run.py --trace
1`` does, prints its result line, and then one JSON line of readings:

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

- ``host_fetches_per_chunk``: device-to-host fetches per chunk served
  (``EngineCounters``, over the window);
- ``prefetch_block_ms``: median of the ``repro.engine.prefetch`` spans,
  the staging of chunk k+1 inside ``run_chunk(k)``;
- ``engine_idle_ms``: the first device's idle seconds whose innermost open
  span, of the benchmark's and the program's, is a program span, per chunk;
- ``plan_host_ms``: median over chunks of the staged plan's host self time,
  the ``repro.plan.`` spans less their ``repro.sync.`` children;
- ``idle_by_span``: that idle time by the innermost span's name;
- ``span_ms``: the median length of each program span, by name;
- ``counters``: each counter's change over the window;
- ``traced``: the end-to-end metrics of this traced window, beside those
  of a run with the profiler off, for the profiler's cost.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import glob
import json
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import bench.run as R  # noqa: E402  (first: its clock starts the set-up)
from bench import devtrace as DT  # noqa: E402
from bench import harness as H  # noqa: E402

PREFIX = "repro."


def program_events(trace_dir: str) -> List[DT.Event]:
    """The program's spans (names starting ``repro.``) of the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    return [DT.Event(plane.name, line.name, e.name, float(e.start_ns),
                     float(e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


@contextlib.contextmanager
def keep_program(got: Dict):
    """While open, a traced run leaves in ``got`` the trace's events
    (``events``), the program's spans (``program``), the window's served
    pass (``rec``) and the engine's counters before and after it."""
    load, serve = DT.load, H.Fleet.serve

    def load_keeping(trace_dir):
        got["program"] = program_events(trace_dir)
        got["events"] = load(trace_dir)
        return got["events"]

    def serve_counting(fleet, *args, record=True, **kw):
        counters = getattr(fleet.engine, "counters", None)
        before = copy.copy(counters)
        rec = serve(fleet, *args, record=record, **kw)
        if record:
            got.update(rec=rec, live=fleet.cell.live, chips=fleet.cell.chips,
                       frames_per_chunk=fleet.n_cameras * fleet.batch,
                       counters=(before, copy.copy(counters)))
        return rec

    DT.load, H.Fleet.serve = load_keeping, serve_counting
    try:
        yield got
    finally:
        DT.load, H.Fleet.serve = load, serve


def _inside(outer: DT.Event, e: DT.Event) -> bool:
    return outer.start_ns <= e.start_ns and e.end_ns <= outer.end_ns


def idle_by_span(trace: DT.Trace, spans: List[DT.Event]) -> Dict[str, float]:
    """The first device's idle seconds by the innermost span open at the
    middle of each gap, of the benchmark's spans and ``spans``."""
    both = copy.copy(trace)
    both.host = trace.host + spans
    return dict(both.idle_gaps(len(both.host) + 1))


def prefetch_block_ms(spans: List[DT.Event]) -> Optional[float]:
    d = [e.dur_ns for e in spans if e.name == "repro.engine.prefetch"]
    return statistics.median(d) / 1e6 if d else None


def plan_host_ms(spans: List[DT.Event]) -> Optional[float]:
    """Median over the ``repro.engine.run_chunk`` spans of the self time
    of the ``repro.plan.`` spans inside each: their duration less that of
    the ``repro.sync.`` spans inside them."""
    plan = [e for e in spans if e.name.startswith("repro.plan.")]
    sync = [e for e in spans if e.name.startswith("repro.sync.")]
    per_chunk = [sum(p.dur_ns - sum(s.dur_ns for s in sync if _inside(p, s))
                     for p in plan if _inside(c, p))
                 for c in spans if c.name == "repro.engine.run_chunk"]
    return statistics.median(per_chunk) / 1e6 if per_chunk else None


def span_ms(spans: List[DT.Event]) -> Dict[str, float]:
    by_name: Dict[str, List[float]] = {}
    for e in spans:
        by_name.setdefault(e.name, []).append(e.dur_ns / 1e6)
    return {n: statistics.median(d) for n, d in sorted(by_name.items())}


def readings(got: Dict) -> Dict:
    """The readings of one window that ``keep_program`` recorded."""
    trace = DT.Trace(got["events"], devices=range(got["chips"]))
    spans = [e for e in got["program"]
             if trace.lo <= e.start_ns and e.end_ns <= trace.hi]
    before, after = got["counters"]
    delta = ({f.name: getattr(after, f.name) - getattr(before, f.name)
              for f in dataclasses.fields(after)}
             if after is not None else None)
    rec = got["rec"]
    chunks = len(rec.chunks)
    idle = idle_by_span(trace, spans)
    out = {"host_fetches_per_chunk":
           delta["host_fetches"] / delta["chunks"]
           if delta and delta["chunks"] else None,
           "prefetch_block_ms": prefetch_block_ms(spans),
           "engine_idle_ms": sum(s for n, s in idle.items()
                                 if n.startswith(PREFIX)) / chunks * 1e3
           if chunks else None,
           "plan_host_ms": plan_host_ms(spans),
           "idle_by_span": idle, "span_ms": span_ms(spans),
           "counters": delta}
    if chunks:
        traced = {"frames_per_s": chunks * got["frames_per_chunk"]
                  / (rec.t_end - rec.t0)}
        if got["live"]:
            traced["latency_p50_ms"] = H.percentile(rec.latencies_s, 50) * 1e3
            traced["latency_p95_ms"] = H.percentile(rec.latencies_s, 95) * 1e3
        out["traced"] = traced
    return out


def main(argv=None) -> int:
    got: Dict = {}
    with keep_program(got):
        rc = R.main(list(sys.argv[1:] if argv is None else argv)
                    + ["--trace", "1"])
    if rc == 0:
        print(json.dumps(readings(got)), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
