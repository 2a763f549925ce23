"""Fleet-scale multi-stream serving benchmark (PR 7 acceptance).

Measures aggregate serving throughput for S concurrent camera streams
through the shared staged plan, across three configurations:

  serial_1dev   one device, each stream its own ``MultiQueryStreamExecutor``
                loop (the pre-fleet serving configuration: S x stages
                dispatches + host syncs per chunk interval)
  group_1dev    one device, ``MultiStreamExecutor`` group engine (stacked
                stream axis, vmapped steps — the stacking-only ablation)
  group_8dev    8 forced host devices, group engine + ``("stream",)`` mesh
                ``shard_map`` + double-buffered prefetch
  fleet_temporal_8dev
                temporal query mix through the sharded group scan path
                (``temporal.advance_group``): answers asserted identical
                to per-stream serial runs, fleet-wide frame skipping and
                signal-eval suppression recorded, plus a single-stream
                scan-vs-numpy ``advance`` microbench

Each configuration runs in a subprocess because ``XLA_FLAGS=
--xla_force_host_platform_device_count=N`` must be set before jax is
imported.  Workers warm the jit caches on a full window before timing,
so the numbers are steady-state serving throughput, not compile time.

The 8-device worker also reports the warm-start comparison: stage order
of a cold engine vs one whose ``SlotStats`` were gossip-merged
(``SlotStats.load_merged``) from synthesized peer snapshots, plus the
``CostModel`` pricing of the sharded steps.

Run:  PYTHONPATH=src python -m benchmarks.multi_stream_serving [--smoke]
JSON: results/bench/multi_stream_serving.json (device topology recorded
next to calibration_info — bench provenance).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

SENTINEL = "MULTI_STREAM_RESULT "
S, BATCH, C, G = 16, 32, 6, 8
WINDOW = 64
TAU = 0.2


def _queries():
    from repro.core import query as Q
    return (
        Q.And((Q.ClassCount(0, Q.Op.GE, 3), Q.Spatial(0, Q.Rel.LEFT, 1))),
        Q.ClassCount(1, Q.Op.LE, 1),
        Q.Or((Q.Count(Q.Op.GE, 10), Q.Region(2, (0, 0, 4, 4), 1))),
        Q.Not(Q.ClassCount(2, Q.Op.GE, 2)),
    )


def _fleet_data(streams, n_frames):
    """Per-stream synthetic filter outputs, mixed skew (rate grows with
    stack position so per-stream undecided fractions differ)."""
    import jax.numpy as jnp
    import numpy as np
    data = {}
    for ctx in streams:
        r = np.random.default_rng(ctx.seed % 2**32)
        rate = 0.3 + 0.1 * ctx.position
        data[ctx.stream_id] = (
            jnp.asarray(r.poisson(rate, (n_frames, C)).astype(np.float32)),
            jnp.asarray((r.random((n_frames, G, G, C)) < 0.05)
                        .astype(np.float32)))
    return data


# --------------------------------------------------------------------------
# Workers (fresh process per device topology)
# --------------------------------------------------------------------------

def _worker_serial(n_frames, warm_frames):
    """S independent single-stream executors on the default device."""
    import numpy as np
    from repro.core import costmodel as CM
    from repro.core.filters import FilterOutputs
    from repro.core.plan import QueryPlan
    from repro.core.streaming import (HoppingWindow,
                                      MultiQueryStreamExecutor,
                                      QueryRegistry)
    from repro.distributed.multistream import route_streams
    from benchmarks.common import device_topology

    queries = _queries()
    streams = route_streams([f"cam{i}" for i in range(S)], 1)
    data = _fleet_data(streams, warm_frames + n_frames)
    window = HoppingWindow(size=WINDOW, advance=WINDOW)
    cm = CM.default_cost_model()

    executors = []
    for ctx in streams:
        registry = QueryRegistry()
        for q in queries:
            registry.register(q)
        c, g = data[ctx.stream_id]

        def factory(qs, slot_stats=None, c=c, g=g):
            staged = QueryPlan(tuple(qs), tau=TAU).build_staged(
                slot_stats, cost_model=cm)

            def engine(idx):
                val = staged.evaluate(FilterOutputs(counts=c[idx],
                                                    grid=g[idx]))
                staged.flush_stats(slot_stats)
                return np.asarray(val)
            return engine

        ex = MultiQueryStreamExecutor(registry, factory, window, BATCH)
        ex.run(warm_frames)             # compile + settle stage order
        executors.append(ex)

    t0 = time.perf_counter()
    for ex in executors:
        ex.run(n_frames)
    wall = time.perf_counter() - t0
    return {"mode": "serial", "fps": S * n_frames / wall, "wall_s": wall,
            "frames": S * n_frames, "sharded": False,
            "topology": device_topology()}


def _worker_group(n_frames, warm_frames, shard):
    """MultiStreamExecutor group engine; mesh-sharded when ``shard``."""
    import jax
    import numpy as np
    from repro.core import costmodel as CM
    from repro.core.filters import FilterOutputs
    from repro.core.stats import SlotStats
    from repro.core.streaming import HoppingWindow, QueryRegistry
    from repro.distributed import sharding as SH
    from repro.distributed.multistream import (MultiStreamExecutor,
                                               ShardedPlanGroupEngine,
                                               plan_group_engine_factory,
                                               route_streams)
    from benchmarks.common import device_topology

    queries = _queries()
    n_slots = jax.device_count()
    streams = route_streams([f"cam{i}" for i in range(S)], n_slots)
    data = _fleet_data(streams, warm_frames + n_frames)
    mesh = SH.stream_mesh() if shard and n_slots > 1 else None

    def fetch(ctx, idx):
        c, g = data[ctx.stream_id]
        return FilterOutputs(counts=c[idx], grid=g[idx])

    registry = QueryRegistry()
    for q in queries:
        registry.register(q)
    ex = MultiStreamExecutor(
        registry, plan_group_engine_factory(fetch, mesh=mesh,
                                            tau=TAU, restage_every=0),
        HoppingWindow(size=WINDOW, advance=WINDOW), BATCH,
        [f"cam{i}" for i in range(S)], n_slots=n_slots)
    ex.run(warm_frames)                 # compile + prefetch path warm

    t0 = time.perf_counter()
    ex.run(n_frames)
    wall = time.perf_counter() - t0

    engine = ex._engine
    report = engine.staged.last_report
    res = {"mode": "group", "fps": S * n_frames / wall, "wall_s": wall,
           "frames": S * n_frames, "sharded": engine.shard_wrap is not None,
           "chunk_batch": report.batch if report else None,
           "cost_run": report.cost_run if report else None,
           "cost_total": report.cost_total if report else None,
           "calibration_info": CM.default_cost_model().describe(),
           "topology": device_topology(mesh)}

    if shard:
        # warm-start gossip: peers whose ledgers say the spatial tier is
        # useless (passes ~always) and region is selective — a
        # warm-started worker should stage differently than a cold one
        from repro.core import query as Q
        peers = []
        with tempfile.TemporaryDirectory() as td:
            for i in range(2):
                st = SlotStats()
                st.observe(Q.Spatial(0, Q.Rel.LEFT, 1), 990 + i, 1000)
                st.observe(Q.Region(2, (0, 0, 4, 4), 1), 5 + i, 1000)
                p = os.path.join(td, f"peer{i}.json")
                st.save(p)
                peers.append(p)
            cold = ShardedPlanGroupEngine(queries, streams, fetch,
                                          slot_stats=SlotStats(), mesh=mesh)
            warm = ShardedPlanGroupEngine(
                queries, streams, fetch,
                slot_stats=SlotStats.load_merged(peers), mesh=mesh)
        res["warm_start"] = {
            "gossip_peers": len(peers),
            "cold_stage_order": cold.stage_order(),
            "warm_stage_order": warm.stage_order(),
            "orders_differ": cold.stage_order() != warm.stage_order()}
    return res


def _temporal_queries():
    """Temporal mix that latches quickly at fleet rates: once every
    stream's every query is window-decided, chunks skip fetch/stack/plan
    outright — the workload that makes frames_skipped move."""
    from repro.core import query as Q
    return (
        Q.Duration(Q.ClassCount(0, Q.Op.GE, 1), 3),
        Q.Or((Q.SlidingCount(Q.ClassCount(1, Q.Op.GE, 1), 6, Q.Op.GE, 2),
              Q.Not(Q.Count(Q.Op.GE, 12)))),
        Q.SlidingCount(Q.Count(Q.Op.GE, 0), 2, Q.Op.GE, 0),
        Q.Sequence(Q.ClassCount(0, Q.Op.GE, 1),
                   Q.ClassCount(2, Q.Op.GE, 1), 5),
    )


def _worker_temporal(n_frames, warm_frames, shard):
    """Fleet-temporal serving: group scan path vs per-stream serial
    reference (answers asserted identical), plus a single-stream
    scan-vs-numpy advance microbench."""
    import jax
    import numpy as np
    from repro.core import costmodel as CM
    from repro.core.filters import FilterOutputs
    from repro.core.plan import QueryPlan
    from repro.core.streaming import (HoppingWindow,
                                      MultiQueryStreamExecutor,
                                      QueryRegistry)
    from repro.core.temporal import TemporalProgram
    from repro.distributed import sharding as SH
    from repro.distributed.multistream import (MultiStreamExecutor,
                                               plan_group_engine_factory,
                                               route_streams)
    from benchmarks.common import device_topology

    queries = _temporal_queries()
    n_slots = jax.device_count()
    stream_ids = [f"cam{i}" for i in range(S)]
    streams = route_streams(stream_ids, n_slots)
    mesh = SH.stream_mesh() if shard and n_slots > 1 else None
    # hotter streams than the filter workload: the latching mix needs
    # activity to decide windows early
    import jax.numpy as jnp
    data = {}
    for ctx in streams:
        r = np.random.default_rng(ctx.seed % 2**32)
        rate = 1.0 + 0.1 * ctx.position
        data[ctx.stream_id] = (
            jnp.asarray(r.poisson(rate, (n_frames, C)).astype(np.float32)),
            jnp.asarray((r.random((n_frames, G, G, C)) < 0.05)
                        .astype(np.float32)))

    def fetch(ctx, idx):
        c, g = data[ctx.stream_id]
        return FilterOutputs(counts=c[idx], grid=g[idx])

    registry = QueryRegistry()
    for q in queries:
        registry.register(q)
    ex = MultiStreamExecutor(
        registry, plan_group_engine_factory(fetch, mesh=mesh,
                                            tau=TAU, restage_every=0),
        HoppingWindow(size=WINDOW, advance=WINDOW), BATCH,
        stream_ids, n_slots=n_slots)
    ex.run(warm_frames)                 # compile scan + staged steps
    ex._engine.temporal_stats.__init__()    # steady-state stats only

    t0 = time.perf_counter()
    results = ex.run(n_frames)
    wall = time.perf_counter() - t0
    ts = ex._engine.temporal_stats

    # identity: per-stream serial masks-as-answers reference (numpy
    # backend — the fleet path's differential baseline)
    class SerialEngine:
        def __init__(self, qs, sid):
            self.prog = TemporalProgram(tuple(qs), backend="numpy")
            c, g = data[sid]
            self.masks = np.asarray(QueryPlan(
                tuple(self.prog.frame_queries), tau=TAU).evaluate(
                    FilterOutputs(counts=c, grid=g)))

        def on_window_start(self, lo, hi):
            self.prog.start_window(hi - lo)

        def __call__(self, idx):
            sup = self.prog.suppressed_signals()
            return self.prog.advance(
                self.masks[np.asarray(idx)] & ~sup[None, :])

    for sid in stream_ids:
        reg = QueryRegistry()
        for q in queries:
            reg.register(q)
        serial = MultiQueryStreamExecutor(
            reg, lambda qs, sid=sid: SerialEngine(qs, sid),
            HoppingWindow(size=WINDOW, advance=WINDOW), BATCH).run(n_frames)
        for w, res in enumerate(results):
            assert res.span == serial[w].span
            assert res.hits[sid] == serial[w].hits, (sid, w)

    # scan-vs-loop advance microbench (single stream, steady state)
    prog_sig = np.random.default_rng(0)
    reps = 3 if n_frames <= 128 else 10
    times = {}
    for backend in ("scan", "numpy"):
        prog = TemporalProgram(queries, backend=backend)
        sig = prog_sig.random((WINDOW, prog.n_signals)) < 0.5

        def one_window(prog=prog, sig=sig):
            prog.start_window(WINDOW)
            for b0 in range(0, WINDOW, BATCH):
                prog.advance(sig[b0:b0 + BATCH])
        one_window()                    # trace/warm
        t0 = time.perf_counter()
        for _ in range(reps):
            one_window()
        times[backend] = (time.perf_counter() - t0) / reps

    return {"mode": "temporal", "fps": S * n_frames / wall,
            "wall_s": wall, "frames": S * n_frames,
            "sharded": ex._engine.shard_wrap is not None,
            "identity_streams": S,
            "frames_in": ts.frames_in,
            "frames_skipped": ts.frames_skipped,
            "signal_evals_skipped": ts.signal_evals_skipped,
            "cost_saved_model": ts.cost_saved_model,
            "cost_temporal_model": ts.cost_temporal_model,
            "scan_advance_ms": times["scan"] * 1e3,
            "numpy_advance_ms": times["numpy"] * 1e3,
            "scan_vs_loop_speedup": times["numpy"] / times["scan"],
            "calibration_info": CM.default_cost_model().describe(),
            "topology": device_topology(mesh)}


# --------------------------------------------------------------------------
# Parent: spawn one worker per device topology, assemble the JSON
# --------------------------------------------------------------------------

def _spawn(mode, devices, smoke, shard=False):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.multi_stream_serving",
           "--worker", mode, "--devices", str(devices)]
    if smoke:
        cmd.append("--smoke")
    if shard:
        cmd.append("--shard")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=3000)
    for line in r.stdout.splitlines():
        if line.startswith(SENTINEL):
            return json.loads(line[len(SENTINEL):])
    raise RuntimeError(f"worker {mode}/{devices}dev failed:\n"
                       f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")


def run(smoke: bool = False) -> dict:
    from benchmarks.common import emit, save_result

    n_frames = 128 if smoke else 512
    print(f"fleet serving: S={S} streams, batch={BATCH}, "
          f"{n_frames} frames/stream per worker (smoke={smoke})")
    serial = _spawn("serial", 1, smoke)
    group1 = _spawn("group", 1, smoke)
    group8 = _spawn("group", 8, smoke, shard=True)
    tempo8 = _spawn("temporal", 8, smoke, shard=True)

    speedup = group8["fps"] / serial["fps"]
    stacking = group1["fps"] / serial["fps"]
    payload = {
        "streams": S, "batch": BATCH, "frames_per_stream": n_frames,
        "window": WINDOW, "smoke": smoke,
        "serial_1dev": serial, "group_1dev": group1, "group_8dev": group8,
        "fleet_temporal_8dev": tempo8,
        "speedup_8dev_vs_1dev": speedup,
        "speedup_stacking_only_1dev": stacking,
        "warm_start": group8.get("warm_start"),
        "calibration_info": group8["calibration_info"],
        "device_topology": {"serial_1dev": serial["topology"],
                            "group_8dev": group8["topology"]},
    }
    save_result("multi_stream_serving", payload)
    emit("multi_stream_serving/serial_1dev", 1e6 / serial["fps"],
         f"fps={serial['fps']:.0f}")
    emit("multi_stream_serving/group_1dev", 1e6 / group1["fps"],
         f"fps={group1['fps']:.0f};stacking={stacking:.2f}x")
    emit("multi_stream_serving/group_8dev", 1e6 / group8["fps"],
         f"fps={group8['fps']:.0f};speedup={speedup:.2f}x")
    emit("multi_stream_serving/fleet_temporal_8dev", 1e6 / tempo8["fps"],
         f"fps={tempo8['fps']:.0f};"
         f"skipped={tempo8['frames_skipped']}/{tempo8['frames_in']};"
         f"scan_vs_loop={tempo8['scan_vs_loop_speedup']:.2f}x")
    print(f"serial 1dev : {serial['fps']:10.0f} frames/s")
    print(f"group  1dev : {group1['fps']:10.0f} frames/s "
          f"({stacking:.2f}x — stacking-only ablation)")
    print(f"group  8dev : {group8['fps']:10.0f} frames/s "
          f"({speedup:.2f}x vs serial 1dev; sharded="
          f"{group8['sharded']})")
    print(f"temporal8dev: {tempo8['fps']:10.0f} frames/s "
          f"(answers == serial for {tempo8['identity_streams']} streams; "
          f"frames skipped {tempo8['frames_skipped']}/"
          f"{tempo8['frames_in']}, signal evals skipped "
          f"{tempo8['signal_evals_skipped']}; scan-vs-loop advance "
          f"{tempo8['scan_vs_loop_speedup']:.2f}x)")
    ws = payload["warm_start"]
    print(f"warm-start  : cold order {ws['cold_stage_order']} -> "
          f"warm {ws['warm_stage_order']} "
          f"(differ={ws['orders_differ']})")
    print(f"acceptance (>=1.5x at S>={S}): "
          f"{'PASS' if speedup >= 1.5 else 'FAIL'} ({speedup:.2f}x)")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale budget; still writes "
                         "results/bench/multi_stream_serving.json")
    ap.add_argument("--worker", choices=["serial", "group", "temporal"],
                    help="internal: run one timing configuration "
                         "in-process and print its JSON")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--shard", action="store_true")
    args = ap.parse_args()
    if args.worker:
        import jax
        assert jax.device_count() == args.devices, \
            (jax.device_count(), args.devices)
        n_frames = 128 if args.smoke else 512
        warm = WINDOW
        if args.worker == "serial":
            out = _worker_serial(n_frames, warm)
        elif args.worker == "temporal":
            out = _worker_temporal(n_frames, warm, args.shard)
        else:
            out = _worker_group(n_frames, warm, args.shard)
        print(SENTINEL + json.dumps(out, default=str), flush=True)
    else:
        run(smoke=args.smoke)


if __name__ == "__main__":
    main()
