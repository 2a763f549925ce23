"""Readings behind the limits of ``cam_gap`` and ``count_rms_gap``.

    python bench/control.py --workload <cell> --seeds 1 2 3 ... [--control-seeds 1 2 3]

For each seed, on the cell's own footage, weights and chunk size, it
reads the two numbers a run compares:

- the program: the served filter step (bf16 trunk, compiled head) against
  the float32 reference;
- the control (``--control-seeds``): the float32 reference with every
  matmul operand rounded to float8, the next precision below the
  configuration's bf16, against the same float32 reference.

A limit lies above the largest program reading and below the smallest
control reading (PERF.md gives both and the limit).  The benchmark's own
runs never run the control.  It needs the cell's chips, like a run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, control: bool) -> dict:
    """The program's (and optionally the control's) gaps for one seed,
    over the first ``check_frames`` frames of the first camera."""
    import numpy as np
    from bench import harness as H
    from bench.harness import gap, rms_gap
    from bench.model import flat_params
    fleet = H.Fleet(cell, seed)
    n = int(cell.workload["check_frames"])
    B = fleet.batch
    outs = [fleet.forward(0, b0) for b0 in range(0, n, B)]
    got_c = np.concatenate([np.asarray(o.counts) for o in outs])
    got_g = np.concatenate([np.asarray(o.grid) for o in outs])
    frames = fleet.footage.pools[0][:n]
    ref = H.load_reference(cell)
    w = flat_params(fleet.params)
    want_c, want_g = (np.asarray(x) for x in
                      ref.run(ref.make_forward(cell.config), w, frames))
    out = {"seed": seed, "program": {"cam_gap": gap(got_g, want_g),
                                     "count_gap": gap(got_c, want_c),
                                     "count_rms_gap": rms_gap(got_c, want_c)}}
    if control:
        c_c, c_g = (np.asarray(x) for x in ref.run(
            ref.make_forward(cell.config, control=True), w, frames))
        out["control"] = {"cam_gap": gap(c_g, want_g),
                          "count_gap": gap(c_c, want_c),
                          "count_rms_gap": rms_gap(c_c, want_c)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    from bench import harness as H
    cell = H.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = [readings(cell, s, s in args.control_seeds)
            for s in sorted(set(args.seeds) | set(args.control_seeds))]
    for r in rows:
        print(json.dumps(r), flush=True)
    for who in ("program", "control"):
        got = [r[who] for r in rows if who in r]
        if got:
            print(f"{who}: " + ", ".join(
                f"{k} max {max(g[k] for g in got):.6g} min "
                f"{min(g[k] for g in got):.6g}" for k in got[0]),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
