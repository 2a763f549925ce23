"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell (weights from the seed on the device, footage, queries,
compiles through the persistent cache at ``<checkout>/.jax_cache``),
warms up every shape the window uses, measures for ``--seconds``, checks
the answers against the plain references, and prints one JSON line last
on standard output.  With ``--trace 1`` the window is recorded by the
profiler and the line carries the cell's per-layer metrics; with
``--trace 0`` its end-to-end metrics.  Without a TPU, or with fewer chips
than the cell asks for, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


RESTAGE_EVERY = 16     # the fleet engine re-sorts its plan every 16 chunks


def warm_up(fleet, seconds: float, window_frames) -> int:
    """Serves the fleet before the clock starts, with every frame present;
    returns the chunks served.  The first pass compiles (or loads from the
    cache) every program of the chunk path.

    Where the window would reach the plan's re-staging, warm-up serves
    whole passes over the footage pool in one pass, past the first
    re-staging.  A re-staging re-sorts each stage's slots by their pass
    rates, and a new order compiles new steps; with the window starting
    at the pool's start, every re-staging in it sees whole passes over
    the pool, so the rates it sorts by barely move and no order is new.
    A live window's frames per camera are given; an archive window's
    follow from the time per chunk of one hopping window, served first."""
    from bench.traffic import Schedule
    S, B, W = fleet.n_cameras, fleet.batch, fleet.window
    done = 0
    if window_frames is None:
        t0 = time.perf_counter()
        fleet.serve(W, Schedule(S, None), record=False)
        done = W // B
        per_chunk = (time.perf_counter() - t0) / done
        window_frames = (seconds / per_chunk + done) * B
    if done + window_frames / B > RESTAGE_EVERY:
        cycle = fleet.pool_frames // B
        chunks = -(-(RESTAGE_EVERY + 1) // cycle) * cycle
        fleet.serve(chunks * B, Schedule(S, None), record=False)
        done += chunks
    elif done == 0:
        fleet.serve(W, Schedule(S, None), record=False)
        done = W // B
    return done


class Compiles:
    """Programs that JAX compiles, or loads from the persistent cache,
    while ``on`` (``/jax/core/compile/backend_compile_duration`` events),
    with their seconds."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on, self.count, self.seconds = False, 0, 0.0

        def listen(event, duration, **_):
            if self.on and event == self.EVENT:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


class Pauses:
    """Python's garbage collections while ``on``: the full (generation 2)
    ones and the longest pause of any."""

    def __init__(self):
        self.on, self.full, self.longest, self._t0 = False, 0, 0.0, None
        gc.callbacks.append(self._observe)

    def _observe(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.on and self._t0 is not None:
            self.longest = max(self.longest, time.perf_counter() - self._t0)
            self.full += info["generation"] == 2


def cpu_throttled_s() -> float:
    """Seconds this process's control group has been held back by its CPU
    quota (cgroup v2 ``cpu.stat``), 0 where that is not to be read."""
    try:
        with open("/sys/fs/cgroup/cpu.stat") as f:
            stat = dict(line.split() for line in f)
        return int(stat.get("throttled_usec", 0)) / 1e6
    except (OSError, ValueError):
        return 0.0


def measure(cell, seed: int, seconds: float, trace: bool,
            t_start: float) -> dict:
    """Set-up, warm-up, window, check and metrics of one run."""
    import jax
    import numpy as np
    from bench import harness as H
    from bench.flops import filter_flops_per_frame
    from bench.traffic import Schedule

    compiles = Compiles()
    compiles.on = True
    fleet = H.Fleet(cell, seed)
    H.log(f"setup: weights and footage ready at "
          f"{time.perf_counter() - t_start:.3f} s")
    S, B = fleet.n_cameras, fleet.batch
    rate = cell.workload["rate_fps"] if cell.live else None
    n = max(B, round(rate / S * seconds / B) * B) if cell.live else 10 ** 9
    warm = warm_up(fleet, seconds, n if cell.live else None)
    ts = fleet.engine.temporal_stats
    ts0 = (ts.frames_in, ts.frames_skipped) if ts is not None else (0, 0)
    ec = fleet.engine.counters
    ec0 = dataclasses.asdict(ec)
    # The warmed-up heap (JAX's caches and traced programs, the footage,
    # the plan) is collected once and frozen, as a long-running server
    # does after warm-up, so that no full collection walks it inside the
    # window; the collections left there are logged.
    gc.collect()
    gc.freeze()
    pauses = Pauses()
    setup_s = time.perf_counter() - t_start
    H.log(f"setup: {setup_s:.3f} s (warm-up: {warm} chunks; "
          f"{compiles.count} programs compiled or loaded, "
          f"{compiles.seconds:.3f} s)")
    compiles.count, compiles.seconds = 0, 0.0
    throttled0 = cpu_throttled_s()
    pauses.on = True

    schedule = Schedule(S, rate)
    deadline = None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    if not cell.live:
        deadline = time.perf_counter() + seconds
    rec = fleet.serve(n, schedule, deadline_s=deadline)
    if trace:
        jax.profiler.stop_trace()
    compiles.on = pauses.on = False
    H.log(f"window: {pauses.full} full garbage collections, longest "
          f"collection {pauses.longest * 1e3:.3f} ms; CPU quota held the "
          f"process back {cpu_throttled_s() - throttled0:.3f} s")
    late = sorted(schedule.late_s)
    H.log(f"window: {len(rec.chunks)} chunks, {rec.forwards} filter "
          f"forwards, {compiles.count} programs compiled or loaded "
          f"({compiles.seconds:.3f} s); generator woke late by "
          f"median {H.percentile(late, 50) * 1e3 if late else 0:.3f} ms, "
          f"max {late[-1] * 1e3 if late else 0:.3f} ms over "
          f"{len(late)} waits")
    if rec.chunks:
        gaps = np.diff([rec.t0] + [c.t_answer for c in rec.chunks]) * 1e3
        H.log(f"window: {rec.t_end - rec.t0:.3f} s to the last answer; "
              f"ms between answers: first {gaps[0]:.1f}, then median "
              f"{np.median(gaps[1:]) if len(gaps) > 1 else 0:.1f}, max "
              f"{gaps[1:].max() if len(gaps) > 1 else 0:.1f}")
    if cell.live and rec.latencies_s:
        tenths = np.array_split(np.asarray(rec.latencies_s) * 1e3, 10)
        H.log("window: latency median by tenth of the window (ms): "
              + " ".join(f"{np.median(t):.1f}" for t in tenths if len(t)))
    devices = jax.devices()
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    frames = len(rec.chunks) * S * B
    run = H.Run(cell=cell, rec=rec, frames_answered=frames,
                window_s=rec.t_end - rec.t0,
                temporal=None if ts is None else
                {"frames_in": ts.frames_in - ts0[0],
                 "frames_skipped": ts.frames_skipped - ts0[1]},
                counters={k: v - ec0[k]
                          for k, v in dataclasses.asdict(ec).items()},
                trace=None, peak=H.peaks(devices[0].device_kind, cell.root),
                flops_per_frame=filter_flops_per_frame(
                    cell.config, cell.config["filter"]["d_embed"],
                    cell.root),
                batch=B)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak_mem)}
    result = {"correct": False, "attempted": n * S if cell.live else frames,
              "failed": n * S - frames if cell.live else 0}
    if trace:
        from bench import devtrace as DT
        t_read = time.perf_counter()
        run.trace = DT.Trace(DT.load(trace_dir),
                             devices=range(cell.chips))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        metrics = {}
        for m in cell.per_layer:
            v = H.load_reader(m["name"], cell.root)(run)
            if H.is_finite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_gaps(10)}
        H.log(f"trace: {len(run.trace.events)} events read and reduced "
              f"in {time.perf_counter() - t_read:.3f} s")
    else:
        e2e = H.end_to_end(run, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    for name, m in metrics.items():
        H.log(f"metric {name} = {m['value']} {m['unit']}")
    fleet.executor = fleet.engine = None    # free the program's state
    t_check = time.perf_counter()
    checks = H.check(fleet, rec, cell.workload["limits"],
                     int(cell.workload["check_frames"]))
    H.log(f"check: {time.perf_counter() - t_check:.3f} s")
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    result.update(metrics=metrics, device=device)
    if "breakdown" in result:
        result["breakdown"] = result.pop("breakdown")
    result["checks"] = checks
    for name, c in checks.items():
        H.log(f"check {name} = {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness as H
    cell = H.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    H.log(f"setup: backend up at {time.perf_counter() - T_START:.3f} s")
    from repro.compile_cache import enable_compile_cache
    H.log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    H.log(f"device: {devices[0].platform} {devices[0].device_kind} x "
          f"{len(devices)}; cell {cell.name}, seed {args.seed}, "
          f"{args.seconds} s, trace {args.trace}")
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
