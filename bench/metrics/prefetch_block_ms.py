"""Median length of the program's ``repro.engine.prefetch`` spans in the
traced window: the staging of chunk k+1 inside ``run_chunk(k)``, which
holds back chunk k's answer (``bench/program_spans.py``)."""
from bench import program_spans as PS


def read(run):
    if run.trace is None:
        return None
    return PS.prefetch_block_ms(run.trace.program)
