"""Find a live cell's knee: the highest offered rate it sustains.

    python bench/sweep.py --workload <live cell> --seed <n> --seconds <s> --rates 100 120 ...

Sets the cell up once and serves one window per offered rate (frames per
second over the whole fleet), in the order given.  For each rate it
prints the answered rate, latency p50/p95, and how the latency of the
window's last fifth of frames compares with its first fifth: a backlog
that grows through the window shows as a ratio well above 1.  The knee
found this way is recorded in the cell's workload file, whose offered
rate is then fixed at four fifths of it; the benchmark never searches.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from bench import harness as H
    from bench.traffic import Schedule
    cell = H.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu" or not cell.live:
        print("sweep: needs a TPU and a live cell", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    fleet = H.Fleet(cell, args.seed)
    S, B = fleet.n_cameras, fleet.batch
    for _ in range(2):
        fleet.serve(17 * B, Schedule(S, None), record=False)
    for rate in args.rates:
        n = max(B, round(rate / S * args.seconds / B) * B)
        rec = fleet.serve(n, Schedule(S, rate), record=False)
        lat = np.asarray(rec.latencies_s)
        k = max(1, len(lat) // 5)
        print(json.dumps({
            "offered_fps": rate, "answered_fps":
            len(rec.chunks) * S * B / (rec.t_end - rec.t0),
            "latency_p50_ms": float(np.percentile(lat, 50) * 1e3),
            "latency_p95_ms": float(np.percentile(lat, 95) * 1e3),
            "last_over_first": float(lat[-k:].mean() / lat[:k].mean())}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
