"""``kernels/spatial_predicate.py``'s share of its roofline: the least
time its calls (full batch, or gathered rows with the row indices
prefetched) in the traced window could take on this chip, over their
device time.  Its calls are the Pallas ops whose result ends in the five
statistics per class."""
import math

from bench import devtrace as DT
from bench import flops as FL


def read(run):
    if run.trace is None:
        return None
    least = took = 0.0
    for e in run.trace.ops():
        call = DT.custom_call(e)
        if call is None or DT.op_name(e.name).startswith("cam_head"):
            continue
        results, operands = call
        if not results or results[0][1][-1:] != (5,):
            continue
        rows = operands[0][0] == "s32"
        grid = operands[1][1] if rows else operands[0][1]
        frames = operands[0][1][0] if rows else math.prod(grid[:-2])
        least += FL.roofline_s(FL.spatial_stats_cost(frames, grid[-2],
                                                     grid[-1]), run.peak)
        took += e.dur_ns / 1e9
    return 100.0 * least / took if took > 0 else None
