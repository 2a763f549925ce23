"""Device-to-host fetches per chunk served: the change of the fleet
engine's ``EngineCounters.host_fetches`` over the window, over the change
of its ``chunks`` (``bench/program_spans.py``)."""
from bench import program_spans as PS


def read(run):
    return PS.host_fetches_per_chunk(run.counters)
