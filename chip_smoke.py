#!/usr/bin/env python3
"""Smoke run of the served query path on a TPU, at Qwen2-0.5B filter width.

    python chip_smoke.py [--seed N]       # one chip: every phase below
    python chip_smoke.py --four-chips     # four chips: the sharded fleet
                                          #   path and its one-device
                                          #   reference, nothing else

The filter model is Qwen2-0.5B's trunk at its published widths in bf16
(``configs/qwen2_0p5b.py``: 24 layers, d_model 896, 14 heads over 2 KV
heads, d_ff 4864) with random weights from ``--seed``; the IC branch taps
it after layer 5 and emits counts plus a 56x56 CAM over 8 classes through
the compiled CAM-head kernel.  Frames come from the synthetic scene
generator at the branch's grid and class count, seeded the same way.

Phases, all in this one process (a chip belongs to one process):

1. fleet: 4 camera streams served by ``MultiStreamExecutor`` with
   ``plan_group_engine_factory``; each chunk's ``fetch`` runs the jitted
   filter forward on the device.  The query mix (count, class-count,
   ``Spatial``, ``Region`` and a temporal ``Duration``) makes the count,
   spatial, ``region@r`` and temporal-scan tiers run.  A second pass over
   the same registry reuses every compiled step and gives a steady
   frames/s: a smoke number from the host clock, not a benchmark.
2. one stream through ``MultiQueryStreamExecutor`` and
   ``MultiQueryExecutor`` with the region-bearing queries of the mix, so
   the union-mask oracle compaction runs and leaves frames out.  The
   oracle is the generator's ground-truth object lookup, not a model.
3. checks: staged answers equal the exhaustive ``QueryPlan.evaluate``
   answers frame for frame (through the temporal replay specification
   for the fleet, through the exact object semantics for the oracle
   path); both spatial kernels equal ``ref.spatial_stats_ref`` exactly;
   the compiled CAM head matches the XLA head within an f32 tolerance;
   the bf16 filter's counts match a float32 forward; and the compiled
   served steps contain the TPU kernels (``tpu_custom_call``).

Staging decisions are priced by the static cost model: no calibration is
committed, and a local (git-ignored) one must not change this run.

The script exits non-zero, printing no result, when JAX finds no TPU (or
fewer than four for ``--four-chips``) or when any phase fails.  Its last
stdout line is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import costmodel as CM  # noqa: E402
from repro.core import filters as F  # noqa: E402
from repro.core import query as Q  # noqa: E402
from repro.core.cascade import (MultiQueryCascade,  # noqa: E402
                                MultiQueryExecutor)
from repro.core.filters import FilterOutputs  # noqa: E402
from repro.core.plan import QueryPlan  # noqa: E402
from repro.core.streaming import (HoppingWindow,  # noqa: E402
                                  MultiQueryStreamExecutor, QueryRegistry)
from repro.core.temporal import replay_reference  # noqa: E402
from repro.data.synthetic import SceneConfig, VideoStream, collect  # noqa
from repro.distributed import sharding as SH  # noqa: E402
from repro.distributed.multistream import (  # noqa: E402
    MultiStreamExecutor, plan_group_engine_factory)
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.spatial_predicate import (  # noqa: E402
    spatial_stats_bgc, spatial_stats_rows_bgc)
from repro.models.config import ModelConfig  # noqa: E402
from repro.train.filter_train import (filter_forward,  # noqa: E402
                                      filter_tap, init_filter_model)

CONFIG = "qwen2_0p5b"
TAU = 0.2                  # the plan's CAM threshold (paper: 0.2)
WINDOW, BATCH = 32, 8      # hopping window and chunk, in frames
FLEET_STREAMS, FLEET_FRAMES = 4, 64
FOUR_CHIP_STREAMS, FOUR_CHIP_FRAMES = 16, 32
# kernel CAM head vs the XLA head, both at f32 matmul precision: max
# error over the CAM's largest magnitude (f32 sums of D products)
CAM_HEAD_TOL = 1e-4
# bf16 trunk vs the same weights in f32: max count error over the f32
# CAM's largest magnitude.  bf16 keeps 8 mantissa bits, so each rounding
# costs up to 2^-9 relative; five residual layers compound that to about
# a percent, and the head's mean over g^2 cells averages it down.
BF16_COUNT_TOL = 0.05


def require(ok: bool, what: str) -> None:
    """A failed check ends the run (an exception, so no result prints)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


# --------------------------------------------------------------------------
# inputs: scene, filter model, streams, queries
# --------------------------------------------------------------------------

def scene_for(cfg: ModelConfig, seed: int) -> SceneConfig:
    """A scene at the branch tap's grid and class count.  Class skew is
    Zipf-like (the presets' long tail) and objects move at the presets'
    0.4 cells/frame scaled from their 8-cell grid to this one."""
    br = cfg.branch
    probs = 1.0 / np.arange(1, br.n_classes + 1)
    probs = tuple(float(p) for p in probs / probs.sum())
    return SceneConfig(name=f"{cfg.name}-tap", n_classes=br.n_classes,
                       class_probs=probs, grid=br.grid, mean_objects=6.0,
                       std_objects=3.0, speed=0.4 * br.grid / 8, seed=seed)


class FilterModel:
    """Trunk prefix + IC branch with seeded random weights on the device;
    ``forward`` is the served, jitted filter step (compiled CAM head)."""

    def __init__(self, cfg: ModelConfig, d_in: int, seed: int):
        spec = cfg.branch
        self.cfg, self.spec = cfg, spec
        init = jax.jit(init_filter_model, static_argnums=(1, 2, 3))
        self.params = init(jax.random.PRNGKey(seed), cfg, spec, d_in)
        self.step = jax.jit(lambda p, e: filter_forward(p, cfg, spec, e,
                                                        use_kernel=True))
        self.tap = jax.jit(lambda p, e: filter_tap(p, cfg, spec, e))

    def forward(self, embeds) -> FilterOutputs:
        return self.step(self.params, embeds)


def make_streams(scene: SceneConfig, n_streams: int, n_frames: int,
                 seed: int) -> Dict[str, Dict[str, Any]]:
    """Per-camera frames with ground truth: one world, own dynamics."""
    return {f"cam{k}": collect(VideoStream(scene,
                                           dynamics_seed=seed * 1000 + k),
                               n_frames)
            for k in range(n_streams)}


def query_mix(n_classes: int, grid: int) -> Tuple[Tuple, Tuple]:
    """(fleet queries, the oracle path's queries).

    The first four are lone leaves of their tiers, so no other tier can
    decide them: every batch runs the count, spatial and region@r0 tiers.
    The fifth gates a region leaf behind a count leaf.  The Duration
    query runs the temporal scan.

    At tau = 0.2 a random head's CAM marks 15-70% of the cells of each
    class and its counts round to about one object per frame, so the
    count and spatial queries hold on almost every frame.  The region
    thresholds are set so that, on the seed-0 filter at Qwen2-0.5B
    width, each region leaf holds on some frames and not on others (on a
    TPU v5e: about 22%, 66% and 70% of them, in the order written).  The oracle path takes only the
    region-bearing queries, so its union mask leaves frames out."""
    q = grid // 4
    corner, centre = (0, 0, q, q), (q, q, q + 4, q + 4)
    frame = (
        Q.Count(Q.Op.GE, 1),
        Q.ClassCount(0, Q.Op.LE, 2, tolerance=1),
        Q.Spatial(0, Q.Rel.LEFT, 1, radius=1),
        Q.Region(5 % n_classes, corner, min_count=16),
        Q.And((Q.ClassCount(4 % n_classes, Q.Op.GE, 1),
               Q.Region(2 % n_classes, corner, min_count=4))),
    )
    temporal = Q.Duration(Q.Region(5 % n_classes, centre), 3)
    return frame + (temporal,), frame[3:]


# --------------------------------------------------------------------------
# served paths
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """What one pass over the served path produced."""
    answers: Dict[Any, np.ndarray]            # stream -> (frames, N) bool
    outputs: Dict[Any, Dict[int, FilterOutputs]]   # stream -> chunk start
    stages_ran: set                           # tier names that ran
    bodies: set                               # (tier, evaluation body)
    wall_s: float
    engine: Any = None
    forward_devices: set = dataclasses.field(default_factory=set)
    stack_devices: set = dataclasses.field(default_factory=set)


def _note_report(rec: Served, report) -> None:
    if report is not None:
        rec.stages_ran.update(report.ran)
        rec.bodies.update(zip(report.ran, report.bodies))


def serve_fleet(model: FilterModel, streams: Dict[str, Dict[str, Any]],
                registry: QueryRegistry, n_frames: int, *,
                mesh=None) -> Served:
    """One pass of S streams through the fleet executor; records each
    chunk's (S, B, N) answers and the filter outputs its engine saw."""
    n_q = len(registry)
    rec = Served(answers={sid: np.zeros((n_frames, n_q), bool)
                          for sid in streams},
                 outputs={sid: {} for sid in streams}, stages_ran=set(),
                 bodies=set(), wall_s=0.0)

    def fetch(ctx, idx):
        out = model.forward(streams[ctx.stream_id]["embeds"][idx])
        rec.outputs[ctx.stream_id][int(idx[0])] = out
        rec.forward_devices.update(str(d) for d in out.grid.devices())
        return out

    base = plan_group_engine_factory(fetch, mesh=mesh,
                                     cost_model=CM.static_cost_model())

    def factory(queries, ctxs, slot_stats=None, leaf_table=None,
                step_cache=None):
        eng = base(queries, ctxs, slot_stats=slot_stats,
                   leaf_table=leaf_table, step_cache=step_cache)
        run_chunk = eng.run_chunk

        def recording_run_chunk(idx, next_idx=None):
            ans = run_chunk(idx, next_idx)
            for c in ctxs:
                rec.answers[c.stream_id][idx] = ans[c.position]
            _note_report(rec, eng.staged.last_report)
            if eng._next is not None:       # the prefetched next stack
                rec.stack_devices.add(
                    len(eng._next[1].grid.sharding.device_set))
            return ans

        eng.run_chunk = recording_run_chunk
        rec.engine = eng
        return eng

    ex = MultiStreamExecutor(registry, factory, HoppingWindow(WINDOW, WINDOW),
                             BATCH, list(streams),
                             n_slots=1 if mesh is None else mesh.size)
    t0 = time.perf_counter()
    results = ex.run(n_frames)
    rec.wall_s = time.perf_counter() - t0
    if rec.engine.temporal is not None:
        rec.stages_ran.add("temporal-scan")
    qids = [qid for qid, _ in registry.active()]
    for res in results:                 # the executor's own window tallies
        lo, hi = res.span
        for sid, hits in res.hits.items():
            got = rec.answers[sid][lo:hi].sum(0)
            require(all(hits[q] == got[k] for k, q in enumerate(qids)),
                    f"window hits of {sid} in [{lo}, {hi}) match its "
                    f"per-frame answers")
    return rec


def serve_single(model: FilterModel, stream: Dict[str, Any],
                 registry: QueryRegistry, n_frames: int) -> Served:
    """One stream through the shared cascade with the union-mask oracle
    compaction.  The oracle is the generator's ground-truth lookup."""
    n_q = len(registry)
    rec = Served(answers={0: np.zeros((n_frames, n_q), bool)},
                 outputs={0: {}}, stages_ran=set(), bodies=set(),
                 wall_s=0.0)
    g, C = model.spec.grid, model.spec.n_classes

    def factory(queries, slot_stats=None, leaf_table=None, step_cache=None):
        cascade = MultiQueryCascade(queries, tau=TAU, adaptive=True,
                                    slot_stats=slot_stats,
                                    cost_model=CM.static_cost_model(),
                                    leaf_table=leaf_table,
                                    step_cache=step_cache)

        def filter_fn(idx):
            out = model.forward(stream["embeds"][idx])
            rec.outputs[0][int(idx[0])] = out
            return out

        def oracle_fn(idx, sel):
            return [stream["objects"][idx[j]] for j in sel]

        ex = MultiQueryExecutor(cascade, filter_fn, oracle_fn, C, g,
                                oracle_bucket=BATCH)
        rec.engine = ex

        def engine(idx):
            answers = ex.run_batch(idx).answers
            rec.answers[0][idx] = answers
            _note_report(rec, cascade.staging_report)
            return answers

        return engine

    t0 = time.perf_counter()
    MultiQueryStreamExecutor(registry, factory, HoppingWindow(WINDOW, WINDOW),
                             BATCH).run(n_frames)
    rec.wall_s = time.perf_counter() - t0
    return rec


# --------------------------------------------------------------------------
# references: the exhaustive plan over the outputs the engines saw
# --------------------------------------------------------------------------

def _frame_level_nodes(q, acc: Dict) -> None:
    """Every frame-level subtree the replay specification may ask for."""
    if not Q.has_temporal(q):
        acc.setdefault(q, len(acc))
    if isinstance(q, (Q.And, Q.Or)):
        for t in q.terms:
            _frame_level_nodes(t, acc)
    elif isinstance(q, Q.Not):
        _frame_level_nodes(q.term, acc)
    elif isinstance(q, (Q.Duration, Q.SlidingCount)):
        _frame_level_nodes(q.pred, acc)
    elif isinstance(q, Q.Sequence):
        _frame_level_nodes(q.first, acc)
        _frame_level_nodes(q.then, acc)


def exhaustive_masks(preds: Sequence, outputs: Dict[int, FilterOutputs],
                     n_frames: int) -> np.ndarray:
    """(frames, P) exhaustive ``QueryPlan.evaluate`` masks, chunk by
    chunk over exactly the filter outputs the served path saw."""
    evaluate = jax.jit(QueryPlan(tuple(preds), tau=TAU).evaluate)
    masks = np.zeros((n_frames, len(preds)), bool)
    for b0 in range(0, n_frames, BATCH):
        masks[b0:b0 + BATCH] = np.asarray(evaluate(outputs[b0]))
    return masks


def fleet_reference(queries: Sequence, outputs: Dict[int, FilterOutputs],
                    n_frames: int) -> np.ndarray:
    """Per-frame fleet answers by the temporal replay specification over
    exhaustive plan masks (the fleet's filter masks are its answers)."""
    cols: Dict = {}
    for q in queries:
        _frame_level_nodes(q, cols)
    masks = exhaustive_masks(list(cols), outputs, n_frames)
    want = np.zeros((n_frames, len(queries)), bool)
    for lo, hi in HoppingWindow(WINDOW, WINDOW).windows(n_frames):
        for k, q in enumerate(queries):
            want[lo:hi, k] = replay_reference(
                q, lambda p, t: masks[lo + t, cols[p]], hi - lo)
    return want


def oracle_reference(queries: Sequence, outputs: Dict[int, FilterOutputs],
                     objects: Sequence, n_frames: int, n_classes: int,
                     grid: int) -> np.ndarray:
    """Exhaustive filter mask AND the exact object semantics."""
    masks = exhaustive_masks(queries, outputs, n_frames)
    exact = np.array([[Q.eval_objects(q, objects[t], n_classes, grid)
                       for q in queries] for t in range(n_frames)])
    return masks & exact


# --------------------------------------------------------------------------
# kernel and precision checks
# --------------------------------------------------------------------------

def _has_tpu_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


def check_spatial_kernels(grid: jax.Array, seed: int, *,
                          on_chip: bool) -> str:
    """Both spatial kernels equal ``ref.spatial_stats_ref`` exactly, on the
    served CAM and on a seeded grid with mixed occupancy."""
    B = grid.shape[0]
    full = jax.jit(functools.partial(spatial_stats_bgc, tau=TAU,
                                     interpret=not on_chip))
    rows_fn = jax.jit(functools.partial(spatial_stats_rows_bgc, tau=TAU,
                                        interpret=not on_chip))
    want_fn = jax.jit(functools.partial(ref.spatial_stats_ref, tau=TAU))
    rows = jnp.asarray([B - 1, 0, B // 2, 0], jnp.int32)
    seeded = jax.random.normal(jax.random.PRNGKey(seed), grid.shape) * 3
    for name, x in (("served CAM", grid), ("seeded grid", seeded)):
        want = np.asarray(want_fn(x))
        require(np.array_equal(np.asarray(full(x)), want),
                f"spatial_stats_bgc == ref.spatial_stats_ref ({name})")
        require(np.array_equal(np.asarray(rows_fn(x, rows)),
                               want[np.asarray(rows)]),
                f"spatial_stats_rows_bgc == ref rows ({name})")
    note = "exact on served CAM and seeded grid"
    if on_chip:     # the plan's entry points dispatch to the kernels
        inline = jax.jit(lambda x: ops.spatial_stats_inline(x, TAU))
        inline_rows = jax.jit(
            lambda x, r: ops.spatial_stats_rows_inline(x, r, TAU))
        require(_has_tpu_kernel(inline, grid),
                "ops.spatial_stats_inline compiles to tpu_custom_call")
        require(_has_tpu_kernel(inline_rows, grid, rows),
                "ops.spatial_stats_rows_inline compiles to tpu_custom_call")
        note += "; tpu_custom_call in both plan entry points"
    return note


def check_cam_head(model: FilterModel, embeds, *, on_chip: bool) -> str:
    """The kernel CAM head matches the XLA IC head on the same tap, both
    at f32 matmul precision."""
    tap = model.tap(model.params, embeds)
    bp, spec = model.params["branch"], model.spec
    with jax.default_matmul_precision("highest"):
        k = jax.jit(lambda p, t: F.ic_apply(p, t, spec, use_kernel=True))(
            bp, tap)
        x = jax.jit(lambda p, t: F.ic_apply(p, t, spec, use_kernel=False))(
            bp, tap)
    scale = max(float(jnp.abs(x.grid).max()), 1e-6)
    err_cam = float(jnp.abs(k.grid - x.grid).max()) / scale
    err_cnt = float(jnp.abs(k.counts - x.counts).max()) / scale
    require(err_cam <= CAM_HEAD_TOL and err_cnt <= CAM_HEAD_TOL,
            f"cam_head_bgd vs XLA head: rel err cam {err_cam:.3g}, "
            f"counts {err_cnt:.3g} <= {CAM_HEAD_TOL}")
    note = f"rel err cam {err_cam:.3g}, counts {err_cnt:.3g}"
    if on_chip:
        require(_has_tpu_kernel(model.step, model.params, embeds),
                "served filter step compiles to tpu_custom_call")
        note += "; tpu_custom_call in the served filter step"
    return note


def check_bf16(model: FilterModel, embeds, served: FilterOutputs) -> str:
    """The served (bf16 trunk) counts match the same weights in f32."""
    cfg32 = dataclasses.replace(model.cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), model.params)
    spec = model.spec
    with jax.default_matmul_precision("highest"):
        out32 = jax.jit(lambda p, e: filter_forward(
            p, cfg32, spec, e, use_kernel=True))(p32, embeds)
    scale = max(float(jnp.abs(out32.grid).max()), 1e-6)
    err_cnt = float(jnp.abs(served.counts - out32.counts).max()) / scale
    err_cam = float(jnp.abs(served.grid - out32.grid).max()) / scale
    require(err_cnt <= BF16_COUNT_TOL,
            f"bf16 counts vs f32 forward: rel err {err_cnt:.3g} <= "
            f"{BF16_COUNT_TOL}")
    return (f"counts rel err {err_cnt:.3g} (<= {BF16_COUNT_TOL}); CAM rel "
            f"err {err_cam:.3g} (not checked)")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def log(msg: str) -> None:
    print(msg, flush=True)


def build(cfg: ModelConfig, seed: int, n_streams: int, n_frames: int
          ) -> Tuple[FilterModel, Dict[str, Dict[str, Any]]]:
    t0 = time.perf_counter()
    scene = scene_for(cfg, seed)
    streams = make_streams(scene, n_streams, n_frames, seed)
    t1 = time.perf_counter()
    model = FilterModel(cfg, scene.d_embed, seed)
    jax.block_until_ready(model.params)
    t2 = time.perf_counter()
    n_params = sum(x.size for x in jax.tree.leaves(model.params))
    log(f"setup: {n_streams} streams x {n_frames} frames generated in "
        f"{t1 - t0:.3f} s; {n_params} random params initialised on "
        f"{jax.devices()[0].platform} in {t2 - t1:.3f} s (includes compile)")
    first = next(iter(streams.values()))["embeds"][:BATCH]
    t0 = time.perf_counter()
    jax.block_until_ready(model.forward(first))
    t1 = time.perf_counter()
    jax.block_until_ready(model.forward(first))
    t2 = time.perf_counter()
    log(f"setup: filter forward (batch {BATCH}) first call "
        f"{t1 - t0:.3f} s (compile + run), second call {t2 - t1:.4f} s")
    return model, streams


def one_chip_phases(cfg: ModelConfig, seed: int, *, on_chip: bool,
                    n_streams: int = FLEET_STREAMS,
                    n_frames: int = FLEET_FRAMES) -> None:
    """Every one-chip phase; raises on the first failed check."""
    br = cfg.branch
    log(f"config: {cfg.name} trunk, {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} KV, d_ff "
        f"{cfg.d_ff}, {cfg.dtype}; IC branch after layer {br.layer}, grid "
        f"{br.grid}, {br.n_classes} classes, head width {br.head_dim}")
    model, streams = build(cfg, seed, n_streams, n_frames)
    fleet_qs, oracle_qs = query_mix(br.n_classes, br.grid)

    # -- fleet: cold pass (compiles), then a steady pass on the same
    # registry, whose step cache already holds every compiled step
    registry = QueryRegistry()
    registry.register_many(fleet_qs)
    cold = serve_fleet(model, streams, registry, n_frames)
    steady = serve_fleet(model, streams, registry, n_frames)
    frames = n_streams * n_frames
    log(f"fleet: {n_streams} streams x {n_frames} frames, cold pass "
        f"{cold.wall_s:.3f} s (set-up: includes compiles), steady pass "
        f"{steady.wall_s:.3f} s = {frames / steady.wall_s:.1f} frames/s "
        f"(smoke number from the host clock, not a benchmark)")
    log(f"fleet: tier bodies {sorted(cold.bodies)}; filter forward ran "
        f"on {sorted(cold.forward_devices)}")
    log(f"calibration_info: "
        f"{json.dumps(cold.engine.cost_model.describe())}")
    require(cold.engine.cost_model.source == "static",
            "staging priced by the static cost model")
    need = {"counts", "spatial", "region@r0", "temporal-scan"}
    require(need <= cold.stages_ran, f"tiers {sorted(need)} all ran")
    for sid in streams:
        want = fleet_reference(fleet_qs, cold.outputs[sid], n_frames)
        require(np.array_equal(cold.answers[sid], want),
                f"fleet staged == exhaustive, stream {sid}")
        require(np.array_equal(steady.answers[sid], cold.answers[sid]),
                f"fleet steady pass == cold pass, stream {sid}")
    hits = {sid: cold.answers[sid].sum(0).tolist() for sid in streams}
    log(f"check fleet: staged == exhaustive frame for frame for "
        f"{n_streams} streams x {len(fleet_qs)} queries; hits per query "
        f"{hits}")

    # -- one stream through the union-mask oracle compaction
    sid0 = next(iter(streams))
    reg1 = QueryRegistry()
    reg1.register_many(oracle_qs)
    single = serve_single(model, streams[sid0], reg1, n_frames)
    want = oracle_reference(oracle_qs, single.outputs[0],
                            streams[sid0]["objects"], n_frames,
                            br.n_classes, br.grid)
    require(np.array_equal(single.answers[0], want),
            "oracle path staged == exhaustive")
    stats = single.engine.stats
    log(f"check oracle path: staged == exhaustive frame for frame, "
        f"{len(oracle_qs)} queries x {n_frames} frames; the union mask "
        f"passed {stats.filter_pass} of {n_frames} frames to the oracle "
        f"(ground-truth lookup), {stats.oracle_calls} with bucket padding; "
        f"tier bodies {sorted(single.bodies)}")

    # -- kernels and precision, on the first served chunk
    embeds = streams[sid0]["embeds"][:BATCH]
    served = cold.outputs[sid0][0]
    log(f"check spatial kernels: "
        f"{check_spatial_kernels(served.grid, seed, on_chip=on_chip)}")
    log(f"check CAM head: {check_cam_head(model, embeds, on_chip=on_chip)}")
    log(f"check bf16 filter: {check_bf16(model, embeds, served)}")


def four_chip_phase(cfg: ModelConfig, seed: int, *, n_devices: int = 4,
                    n_streams: int = FOUR_CHIP_STREAMS,
                    n_frames: int = FOUR_CHIP_FRAMES) -> None:
    """The fleet path sharded over a ("stream",) mesh of ``n_devices``,
    next to its unsharded one-device reference: identical answers."""
    model, streams = build(cfg, seed, n_streams, n_frames)
    fleet_qs, _ = query_mix(cfg.branch.n_classes, cfg.branch.grid)
    mesh = SH.stream_mesh(n_devices)
    regs = [QueryRegistry(), QueryRegistry()]
    for r in regs:
        r.register_many(fleet_qs)
    sharded = serve_fleet(model, streams, regs[0], n_frames, mesh=mesh)
    plain = serve_fleet(model, streams, regs[1], n_frames)
    eng = sharded.engine
    require(eng._sharding is not None and eng.shard_wrap is not None,
            f"{n_streams} streams sharded over the {n_devices}-device mesh")
    require(sharded.stack_devices == {n_devices},
            f"stacked filter outputs span {n_devices} devices "
            f"(saw {sorted(sharded.stack_devices)})")
    for sid in streams:
        require(np.array_equal(sharded.answers[sid], plain.answers[sid]),
                f"sharded == unsharded answers, stream {sid}")
    log(f"four chips: {n_streams} streams x {n_frames} frames on mesh "
        f"{dict(mesh.shape)}; stack sharding {eng._sharding.spec} over "
        f"{sorted(sharded.stack_devices)} devices; per-stream answers "
        f"identical to the unsharded reference; sharded pass "
        f"{sharded.wall_s:.3f} s, unsharded {plain.wall_s:.3f} s (cold, "
        f"include compiles); filter forward ran on "
        f"{sorted(sharded.forward_devices)}")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the frames")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the stream-sharded fleet path on 4 "
                         "chips, next to its one-device reference")
    args = ap.parse_args(argv)
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache: {cache} ({n_cached} entries at start)")
    log(f"device: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}")
    cfg = get_config(CONFIG)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(cfg, args.seed)
    else:
        one_chip_phases(cfg, args.seed, on_chip=True)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
