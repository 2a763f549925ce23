"""The benchmark's harness: a cell's files, its set-up, its measured
window, the check that decides ``correct``, and its metrics.

Everything that belongs to one configuration, traffic mix, query mix,
cell or per-layer metric is a file of its own, found by name:

- ``BENCHMARK.json`` names each cell's configuration and traffic;
- ``bench/configs/<config>.json`` (and the plain reference it names,
  ``bench/configs/<reference>.py``);
- ``bench/archs/<model_type>.py``: the trunk architecture a
  configuration's ``model_type`` names (``bench/arch.py``);
- ``bench/traffic/<traffic>.json``: scene, cameras, arrival mode, window
  and chunk, the query mix;
- ``bench/mixes/<mix>.json``: the queries;
- ``bench/workloads/<cell>.json``: the cell's offered rate and its
  correctness limits;
- ``bench/metrics/<metric>.py``: a per-layer metric's reader, a function
  ``read(run) -> float | None``.

The served path is the program's fleet path: ``MultiStreamExecutor``
with ``plan_group_engine_factory`` (and a ``("stream",)`` mesh where the
traffic asks for one).  Its ``fetch`` is the benchmark's: it moves each
of the chunk's frames from host memory to the device once the frame
exists, and runs the filter step on them.
"""
from __future__ import annotations

import dataclasses
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import answers as A
from bench.arch import load, load_module
from bench.model import filter_step_fn, flat_params, make_params
from bench.seeds import host_rng
from bench.traffic import Schedule, Scene, make_footage

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


# --------------------------------------------------------------------------
# a cell's files
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    workload: Dict[str, Any]
    queries: List[Dict[str, Any]]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path

    @property
    def live(self) -> bool:
        return self.traffic["mode"] == "live"


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """A cell of BENCHMARK.json with its files."""
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return make_cell(name, root / configs[w["config"]]["file"],
                     w["traffic"], int(w["chips"]),
                     _for_cell(bench["end_to_end"], name),
                     _for_cell(bench["per_layer"], name), root)


def make_cell(name: str, config_file: Path, traffic: str, chips: int,
              end_to_end: List[Dict], per_layer: List[Dict],
              root: Path = ROOT) -> Cell:
    """A cell from its files: configuration, traffic, the workload file
    ``bench/workloads/<name>.json`` and the traffic's query mix."""
    t = read_json(root / "bench" / "traffic" / f"{traffic}.json")
    return Cell(
        name=name, chips=chips, config=read_json(config_file), traffic=t,
        workload=read_json(root / "bench" / "workloads" / f"{name}.json"),
        queries=read_json(root / "bench" / "mixes"
                          / f"{t['mix']}.json")["queries"],
        end_to_end=end_to_end, per_layer=per_layer, root=root)


def load_reader(metric: str, root: Path = ROOT) -> Callable:
    return load_module(root / "bench" / "metrics" / f"{metric}.py",
                       f"bench_metric_{metric.replace('.', '_')}").read


def load_reference(cell: Cell):
    ref = cell.config["reference"]
    return load_module(cell.root / "bench" / "configs" / f"{ref}.py",
                       f"bench_reference_{ref}")


def peaks(kind: str, root: Path = ROOT) -> Dict[str, float]:
    table = read_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# --------------------------------------------------------------------------
# what a window recorded
# --------------------------------------------------------------------------

class WindowClosed(Exception):
    """Raised at a hopping window's start once an archive window's time
    is up, so that an archive pass answers whole hopping windows."""


@dataclasses.dataclass
class Chunk:
    b0: int                     # first frame (camera-local index)
    t_answer: float             # answers back on the host
    last_arrival: float         # creation of the chunk's last frame
    ran: List[str]              # plan tiers executed (one host sync each)
    prefetch_wait_s: float      # waits for a later chunk's frames


@dataclasses.dataclass
class Served:
    """One pass of the fleet through the served path."""
    t0: float = 0.0
    chunks: List[Chunk] = dataclasses.field(default_factory=list)
    answers: Dict[Tuple[int, int], np.ndarray] = \
        dataclasses.field(default_factory=dict)      # (cam, b0) -> (B, N)
    outputs: Dict[Tuple[int, int], Any] = \
        dataclasses.field(default_factory=dict)      # (cam, b0) -> outputs
    forwards: int = 0
    current: int = -1
    wait_s: float = 0.0
    latencies_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def t_end(self) -> float:
        return self.chunks[-1].t_answer if self.chunks else self.t0


class Fleet:
    """Set-up of a fleet cell: weights, footage, queries, the served
    filter step and the registry; ``serve`` runs one pass."""

    def __init__(self, cell: Cell, seed: int):
        from repro.core import costmodel as CM
        from repro.core.streaming import QueryRegistry
        from repro.distributed import sharding as SH
        t, cfg = cell.traffic, cell.config
        self.cell = cell
        self.seed = seed
        self.arch = load(cfg, cell.root)
        self.mcfg = self.arch.model_config(cfg)
        self.d_in = cfg["filter"]["d_embed"]
        self.batch = int(t["chunk"])
        self.window = int(t["window"])
        self.n_cameras = int(t["cameras"])
        t0 = time.perf_counter()
        self.params = make_params(self.mcfg, self.d_in, seed, self.arch)
        jax.block_until_ready(self.params)
        t1 = time.perf_counter()
        scene = Scene.from_json(t["scene"])
        self.footage = make_footage(scene, cfg["filter"]["grid"],
                                    self.n_cameras, int(t["pool_frames"]),
                                    seed)
        self.pool_frames = int(t["pool_frames"])
        if self.pool_frames % self.window or self.window % self.batch:
            raise ValueError("pool_frames must hold whole windows of whole "
                             "chunks")
        self.step = filter_step_fn(self.mcfg)
        self.registry = QueryRegistry()
        self.registry.register_many([A.to_query(q) for q in cell.queries])
        self.mesh = SH.stream_mesh(cell.chips) if t.get("mesh") else None
        self.cost_model = CM.static_cost_model()
        self.stream_ids = [f"cam{k}" for k in range(self.n_cameras)]
        self.executor = self.engine = self._pass = None
        log(f"setup: weights {t1 - t0:.3f} s, footage "
            f"{time.perf_counter() - t1:.3f} s")

    def put(self, cam: int, frames, schedule: Optional[Schedule] = None
            ) -> Tuple[List[jax.Array], float]:
        """Moves a camera's frames from host memory to the device, each
        once it exists by ``schedule``; returns them and the seconds
        waited."""
        from jax.profiler import TraceAnnotation
        pool, parts, waited = self.footage.pools[cam], [], 0.0
        for i in frames:
            if schedule is not None:
                with TraceAnnotation("bench.fetch_wait"):
                    waited += schedule.wait_for(cam, int(i))
            with TraceAnnotation("bench.frame_put"):
                parts.append(jax.device_put(pool[int(i) % self.pool_frames]))
        return parts, waited

    def forward(self, cam: int, b0: int):
        """The filter step over one camera's chunk from ``b0`` on."""
        return self.step(self.params,
                         self.put(cam, range(b0, b0 + self.batch))[0])

    def serve(self, n_frames: int, schedule: Schedule,
              deadline_s: Optional[float] = None, record: bool = True,
              fault: Optional[Callable] = None) -> Served:
        """One pass: ``n_frames`` per camera (an archive pass stops at the
        first chunk boundary past ``deadline_s``).  Every pass goes through
        the one executor and engine, so the window's engine is the one
        that warm-up already ran."""
        from jax.profiler import TraceAnnotation
        rec = Served()
        self._pass = (rec, schedule, deadline_s, record, fault)
        if self.executor is None:
            self.executor = self._executor()
        self.executor._refresh()    # the engine exists before the clock
        rec.t0 = time.perf_counter()
        schedule.start(rec.t0)
        with TraceAnnotation("bench.window"):
            try:
                self.executor.run(n_frames)
            except WindowClosed:
                pass
        return rec

    def _executor(self):
        """The fleet executor over the program's group engine, with the
        benchmark's ``fetch`` and a recording ``run_chunk``; both read the
        current pass from ``self._pass``."""
        from jax.profiler import TraceAnnotation
        from repro.core.streaming import HoppingWindow
        from repro.distributed.multistream import (
            MultiStreamExecutor, plan_group_engine_factory)
        cams = {sid: k for k, sid in enumerate(self.stream_ids)}

        def fetch(ctx, idx):
            rec, schedule, _, record, fault = self._pass
            cam, b0 = cams[ctx.stream_id], int(idx[0])
            frames, waited = self.put(cam, idx, schedule)
            if b0 != rec.current:
                rec.wait_s += waited
            with TraceAnnotation("bench.filter_dispatch"):
                out = self.step(self.params, frames)
            if fault is not None:
                out = fault("outputs", out)
            rec.forwards += 1
            if record:
                rec.outputs[(cam, b0)] = out
            return out

        base = plan_group_engine_factory(fetch, mesh=self.mesh,
                                         cost_model=self.cost_model)

        def factory(queries, ctxs, **kw):
            eng = base(queries, ctxs, **kw)
            inner = eng.run_chunk
            pos = {c.position: cams[c.stream_id] for c in ctxs}

            def run_chunk(idx, next_idx=None):
                rec, schedule, deadline_s, record, fault = self._pass
                b0 = int(idx[0])
                if deadline_s is not None and b0 % self.window == 0 \
                        and time.perf_counter() >= deadline_s:
                    raise WindowClosed
                rec.current, rec.wait_s = b0, 0.0
                with TraceAnnotation("bench.run_chunk"):
                    ans = inner(idx, next_idx)
                if fault is not None:
                    ans = fault("answers", ans)
                t = time.perf_counter()
                rep = eng.staged.last_report
                last = max(schedule.stamp(c, int(idx[-1]))
                           for c in range(self.n_cameras))
                rec.chunks.append(Chunk(b0, t, last, list(rep.ran)
                                        if rep is not None else [],
                                        rec.wait_s))
                for p, cam in pos.items():
                    if record:
                        rec.answers[(cam, b0)] = np.array(ans[p])
                    rec.latencies_s.extend(
                        t - schedule.stamp(cam, int(i)) for i in idx)
                return ans

            eng.run_chunk = run_chunk
            self.engine = eng
            return eng

        return MultiStreamExecutor(
            self.registry, factory,
            HoppingWindow(self.window, self.window, emit_partial=True),
            self.batch, self.stream_ids, n_slots=self.cell.chips)


# --------------------------------------------------------------------------
# correct: the served answers and filter outputs against the references
# --------------------------------------------------------------------------

def _windows(n: int, size: int):
    lo = 0
    while lo < n:
        yield lo, min(lo + size, n)
        lo += size


def gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest gap between served and reference values, over the
    reference's largest magnitude."""
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


def rms_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Root mean square of the gap between served and reference values,
    over the reference's root mean square."""
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-6))


def check(fleet: Fleet, rec: Served, limits: Dict[str, float],
          n_check_frames: int) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each with its limit.

    - ``answer_mismatches``: over every answered frame of every camera,
      the (frame, query) answers that differ from the plain reference
      evaluated on the filter outputs the served path produced;
    - ``cam_gap``: over a seeded sample of answered chunks, the largest
      gap between a served class map and the float32 reference forward of
      the same frames, over the reference's largest magnitude;
    - ``count_rms_gap``: over the same sample, the root mean square gap
      between the served counts and the reference's, over the
      reference's root mean square.  The largest gap of a count does not
      separate the program from the control: a count is a mean over the
      grid, which averages the control's rounding away."""
    cell = fleet.cell
    B = fleet.batch
    done = sorted({c.b0 for c in rec.chunks})
    mism = 0
    for cam in range(fleet.n_cameras):
        if not done:
            break
        n = done[-1] + B
        counts = np.zeros((n, cell.config["filter"]["n_classes"]),
                          np.float32)
        g = cell.config["filter"]["grid"]
        grid = np.zeros((n, g, g, counts.shape[1]), np.float32)
        got = np.zeros((n, len(cell.queries)), bool)
        for b0 in done:
            out = rec.outputs[(cam, b0)]
            counts[b0:b0 + B] = np.asarray(out.counts)
            grid[b0:b0 + B] = np.asarray(out.grid)
            got[b0:b0 + B] = rec.answers[(cam, b0)]
        want = A.reference_answers(cell.queries, counts, grid,
                                   list(_windows(n, fleet.window)))
        mism += int((want != got).sum())
    rng = host_rng(fleet.seed, "check")
    picks = [(int(rng.integers(fleet.n_cameras)), done[int(i)])
             for i in rng.permutation(len(done))[:max(1, n_check_frames // B)]]
    served = {k: (np.asarray(rec.outputs[k].counts),
                  np.asarray(rec.outputs[k].grid)) for k in picks}
    frames = {k: fleet.footage.pools[k[0]][k[1] % fleet.pool_frames:
                                           k[1] % fleet.pool_frames + B]
              for k in picks}
    rec.outputs.clear()
    ref = load_reference(cell)
    weights = flat_params(fleet.params)
    fwd = ref.make_forward(cell.config)
    cam_gap, counts = 0.0, []
    for k in picks:
        want_c, want_g = (np.asarray(x) for x in
                          ref.run(fwd, weights, frames[k]))
        got_c, got_g = served[k]
        counts.append((got_c, want_c))
        cam_gap = max(cam_gap, gap(got_g, want_g))
    count_rms = rms_gap(*(np.concatenate(x) for x in zip(*counts)))
    return {"answer_mismatches": {"value": mism, "limit": 0},
            "cam_gap": {"value": cam_gap, "limit": limits["cam_gap"]},
            "count_rms_gap": {"value": count_rms,
                              "limit": limits["count_rms_gap"]}}


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a per-layer reader reads: the cell, the window's counters
    (``temporal``: the temporal tier's; ``counters``: the change of each
    field of the fleet engine's ``EngineCounters``), the trace
    (``--trace 1``), the FLOP counts and the chip's peaks."""
    cell: Cell
    rec: Served
    frames_answered: int
    window_s: float
    temporal: Optional[Dict[str, int]]
    counters: Dict[str, int]
    trace: Any
    peak: Dict[str, float]
    flops_per_frame: float
    batch: int


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(run: Run, setup_s: float) -> Dict[str, float]:
    out = {"setup_s": setup_s,
           "frames_per_s": run.frames_answered / run.window_s}
    if run.cell.live:
        lat = run.rec.latencies_s
        out["latency_p50_ms"] = percentile(lat, 50) * 1e3
        out["latency_p95_ms"] = percentile(lat, 95) * 1e3
    return out


def is_finite(x: Optional[float]) -> bool:
    return x is not None and math.isfinite(x)
