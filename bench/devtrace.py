"""The reduction from a profiler trace to the numbers the metrics read.

A run with ``--trace 1`` records its measured window with
``jax.profiler`` (Python tracer off, host annotations on).  ``load``
reads the ``.xplane.pb`` with JAX's own ``ProfileData`` and keeps

- every event of a device plane (``/device:TPU:<n>``): its line ("XLA
  Ops", "XLA Modules", ...), name (an op's HLO text), start and duration;
- the host spans the benchmark itself writes (names starting ``bench.``);
- the program's own host spans (names starting ``repro.``; see
  ``repro.tracing``).

``Trace`` keeps the two kinds of host span apart (``host``: the
benchmark's; ``program``: the program's, inside the window) and answers,
over the window span ``bench.window``:

- busy seconds per device: the union of the intervals in which an op ran
  (line "XLA Ops");
- seconds of a jitted program (line "XLA Modules", by module name);
- a Pallas kernel's calls with their shapes (``custom_call``);
- the ops that took most time, and the device's idle gaps by the host
  span that was open in the middle of each gap.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
PROGRAM_PREFIX = "repro."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(pd) -> List[Event]:
    out: List[Event] = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for e in line.events:
                if device or e.name.startswith((BENCH_PREFIX,
                                                 PROGRAM_PREFIX)):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns),
                                     float(e.duration_ns)))
    return out


def save_json(events: Sequence[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_json(path: str) -> List[Event]:
    """Events saved by ``save_json`` (gzip-compressed when the name ends
    in ``.gz``)."""
    with (gzip.open(path, "rt") if path.endswith(".gz") else
          open(path)) as f:
        return [Event(p, l, n, s, d, tuple(tuple(kv) for kv in st))
                for p, l, n, s, d, st in json.load(f)]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def _clip(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def module_base(name: str) -> str:
    """``jit_filter_step(123)`` -> ``jit_filter_step``."""
    return re.sub(r"\(-?\d+\)$", "", name)


def op_name(hlo: str) -> str:
    """``%fusion.305 = bf16[...] fusion(...)`` -> ``fusion.305``."""
    return hlo.split(" = ")[0].lstrip("%")


SHAPE = re.compile(r"\b(pred|[sufb]f?\d+)\[([\d,]*)\]")


def custom_call(e: Event) -> Optional[Tuple[List[Tuple[str, Tuple[int, ...]]],
                                            List[Tuple[str, Tuple[int, ...]]]]]:
    """A Pallas kernel's op (``tpu_custom_call``): its (result shapes,
    operand shapes), each ``(dtype, dims)``; None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in e.name:
        return None
    head, _, rest = e.name.partition(" custom-call(")
    args = rest.split(")", 1)[0]

    def shapes(text):
        return [(t, tuple(int(d) for d in dims.split(",") if d))
                for t, dims in SHAPE.findall(text)]
    return shapes(head.split(" = ", 1)[-1]), shapes(args)


class Trace:
    """One traced window's device events, benchmark host spans and
    program spans."""

    def __init__(self, events: Sequence[Event],
                 devices: Optional[Sequence[int]] = None):
        self.events = list(events)
        spans = [e for e in self.events if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        w = max(spans, key=lambda e: e.dur_ns)
        self.lo, self.hi = w.start_ns, w.end_ns
        planes = sorted({e.plane for e in self.events
                         if DEVICE_PLANE.match(e.plane)},
                        key=lambda p: int(DEVICE_PLANE.match(p).group(2)))
        if devices is not None:
            planes = [p for p in planes
                      if int(DEVICE_PLANE.match(p).group(2)) in devices]
        self.planes = planes
        self.host = [e for e in self.events
                     if e.name.startswith(BENCH_PREFIX)
                     and e.name != WINDOW_SPAN]
        self.program = [e for e in self.events
                        if e.name.startswith(PROGRAM_PREFIX)
                        and not DEVICE_PLANE.match(e.plane)
                        and self.lo <= e.start_ns and e.end_ns <= self.hi]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def ops(self, plane: Optional[str] = None) -> List[Event]:
        return [e for e in self.events if e.line == OPS_LINE
                and (plane is None or e.plane == plane)
                and e.plane in self.planes
                and _clip(e.start_ns, e.end_ns, self.lo, self.hi) > 0]

    def busy_intervals(self, plane: str) -> List[Tuple[float, float]]:
        return _union([(max(e.start_ns, self.lo), min(e.end_ns, self.hi))
                       for e in self.ops(plane)])

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the devices."""
        if not self.planes:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(p))
                   for p in self.planes) / len(self.planes) / 1e9

    def module_s(self, base: str) -> float:
        """Seconds of a jitted program (summed over devices)."""
        return sum(_clip(e.start_ns, e.end_ns, self.lo, self.hi)
                   for e in self.events
                   if e.line == MODULES_LINE and e.plane in self.planes
                   and module_base(e.name) == base) / 1e9

    def module_count(self, base: str) -> int:
        return sum(1 for e in self.events
                   if e.line == MODULES_LINE and e.plane in self.planes
                   and module_base(e.name) == base
                   and _clip(e.start_ns, e.end_ns, self.lo, self.hi) > 0)

    def _module_at(self, plane: str):
        """A lookup from a time on ``plane`` to the program running then."""
        mods = sorted((e.start_ns, e.end_ns, module_base(e.name))
                      for e in self.events
                      if e.line == MODULES_LINE and e.plane == plane)
        starts = [m[0] for m in mods]

        def at(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t <= mods[i][1] else ""
        return at

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ops that took most device time, by self time (a while
        loop's time less the ops inside it), named ``program:op``."""
        tot: Dict[str, float] = {}
        for plane in self.planes:
            at = self._module_at(plane)
            ops = sorted(self.ops(plane), key=lambda e: (e.start_ns,
                                                         -e.dur_ns))
            own = [_clip(e.start_ns, e.end_ns, self.lo, self.hi)
                   for e in ops]
            stack: List[int] = []
            for i, e in enumerate(ops):
                while stack and ops[stack[-1]].end_ns <= e.start_ns:
                    stack.pop()
                if stack:
                    own[stack[-1]] -= own[i]
                stack.append(i)
            for e, t in zip(ops, own):
                key = f"{at(e.start_ns)}:{op_name(e.name)}"
                tot[key] = tot.get(key, 0.0) + t / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """Idle seconds of the first device, by the innermost benchmark
        host span open at the middle of each gap ("none" if no span)."""
        if not self.planes:
            return []
        busy = self.busy_intervals(self.planes[0])
        gaps, t = [], self.lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.hi:
            gaps.append((t, self.hi))
        tot: Dict[str, float] = {}
        for s, e in gaps:
            mid = (s + e) / 2
            open_ = [h for h in self.host if h.start_ns <= mid <= h.end_ns]
            name = min(open_, key=lambda h: h.dur_ns).name if open_ \
                else "none"
            tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
