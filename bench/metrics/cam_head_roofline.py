"""``kernels/cam_head.py``'s share of its roofline: the least time its
calls in the traced window could take on this chip (operations and bytes
from each call's shapes, ``bench/flops.py``) over their device time."""

from bench import devtrace as DT
from bench import flops as FL


def read(run):
    if run.trace is None:
        return None
    least = took = 0.0
    for e in run.trace.ops():
        call = DT.custom_call(e)
        if call is None or not DT.op_name(e.name).startswith("cam_head"):
            continue
        results, operands = call
        frames, g2, d = operands[0][1]
        classes = results[-1][1][-1]
        least += FL.roofline_s(FL.cam_head_cost(frames, g2, d, classes),
                               run.peak)
        took += e.dur_ns / 1e9
    return 100.0 * least / took if took > 0 else None
