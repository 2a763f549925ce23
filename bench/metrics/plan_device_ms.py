"""Device time of the staged plan's tier steps per chunk: the jitted
programs ``jit_plan_<stage>`` (one per tier, ``StagedQueryPlan``), from the
trace.  A program that names its steps otherwise reads nothing."""

from bench import devtrace as DT

PREFIX = "jit_plan_"


def read(run):
    if run.trace is None or not run.rec.chunks:
        return None
    t = run.trace
    bases = {DT.module_base(e.name) for e in t.events
             if e.line == DT.MODULES_LINE and e.plane in t.planes
             and e.name.startswith(PREFIX)}
    if not bases:
        return None
    return sum(t.module_s(b) for b in bases) / len(run.rec.chunks) * 1e3
