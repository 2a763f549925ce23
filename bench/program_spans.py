"""The program's own spans and counters over one traced window.

The fleet path opens ``repro.`` spans at each layer boundary and counts
its work in ``ShardedPlanGroupEngine.counters`` (``repro.tracing``).  A
traced run keeps those spans (``Trace.program``) and the counters' change
over the window (``Run.counters``); the reductions here turn them into
the per-layer metrics of the same names, each read by its own
``bench/metrics/<name>.py``:

- ``host_fetches_per_chunk``: device-to-host fetches per chunk served;
- ``prefetch_block_ms``: median of the ``repro.engine.prefetch`` spans,
  the staging of chunk k+1 inside ``run_chunk(k)``;
- ``engine_idle_ms``: the first device's idle seconds whose innermost open
  span, of the benchmark's and the program's, is a program span, per chunk;
- ``plan_host_ms``: median over chunks of the staged plan's host self time,
  the ``repro.plan.`` spans less their ``repro.sync.`` children.

Run by hand, it serves one traced window as ``bench/run.py --trace 1``
does, prints its result line, and then one JSON line with those four
readings and

- ``idle_by_span``: the idle time by the innermost span's name;
- ``span_ms``: the median length of each program span, by name;
- ``counters``: each counter's change over the window;
- ``traced``: the end-to-end metrics of this traced window, beside those
  of a run with the profiler off, for the profiler's cost.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import contextlib
import copy
import json
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace as DT  # noqa: E402
from bench import harness as H  # noqa: E402

PREFIX = DT.PROGRAM_PREFIX


def _inside(outer: DT.Event, e: DT.Event) -> bool:
    return outer.start_ns <= e.start_ns and e.end_ns <= outer.end_ns


def idle_by_span(trace: DT.Trace, spans: List[DT.Event]) -> Dict[str, float]:
    """The first device's idle seconds by the innermost span open at the
    middle of each gap, of the benchmark's spans and ``spans``."""
    both = copy.copy(trace)
    both.host = trace.host + spans
    return dict(both.idle_gaps(len(both.host) + 1))


def host_fetches_per_chunk(counters: Optional[Dict[str, int]]
                           ) -> Optional[float]:
    if not counters or not counters["chunks"]:
        return None
    return counters["host_fetches"] / counters["chunks"]


def prefetch_block_ms(spans: List[DT.Event]) -> Optional[float]:
    d = [e.dur_ns for e in spans if e.name == "repro.engine.prefetch"]
    return statistics.median(d) / 1e6 if d else None


def engine_idle_ms(trace: DT.Trace, spans: List[DT.Event],
                   chunks: int) -> Optional[float]:
    """Idle milliseconds of the first device per chunk under a program
    span (``idle_by_span``); None where there are no program spans."""
    if not spans or not chunks:
        return None
    idle = idle_by_span(trace, spans)
    return sum(s for n, s in idle.items()
               if n.startswith(PREFIX)) / chunks * 1e3


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


def readings(run) -> Dict:
    """The readings of one traced window's ``Run``."""
    trace, spans = run.trace, run.trace.program
    chunks = len(run.rec.chunks)
    out = {"host_fetches_per_chunk": host_fetches_per_chunk(run.counters),
           "prefetch_block_ms": prefetch_block_ms(spans),
           "engine_idle_ms": engine_idle_ms(trace, spans, chunks),
           "plan_host_ms": plan_host_ms(spans),
           "idle_by_span": idle_by_span(trace, spans),
           "span_ms": span_ms(spans), "counters": run.counters}
    if chunks:
        traced = H.end_to_end(run, 0.0)
        traced.pop("setup_s")
        out["traced"] = traced
    return out


@contextlib.contextmanager
def keep_run(got: Dict):
    """While open, a run leaves the ``Run`` its metrics were read from in
    ``got["run"]``."""
    make = H.Run

    def keeping(*args, **kw):
        got["run"] = make(*args, **kw)
        return got["run"]

    H.Run = keeping
    try:
        yield got
    finally:
        H.Run = make


def main(argv=None) -> int:
    import bench.run as R       # the command itself, not loaded by readers
    got: Dict = {}
    with keep_run(got):
        rc = R.main(list(sys.argv[1:] if argv is None else argv)
                    + ["--trace", "1"])
    if rc == 0:
        print(json.dumps(readings(got["run"])), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
