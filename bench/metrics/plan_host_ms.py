"""Median over chunks of the staged plan's host self time: the
``repro.plan.`` spans inside each ``repro.engine.run_chunk`` less their
``repro.sync.`` children, the device fetches (``bench/program_spans.py``)."""
from bench import program_spans as PS


def read(run):
    if run.trace is None:
        return None
    return PS.plan_host_ms(run.trace.program)
